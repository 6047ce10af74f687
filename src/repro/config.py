"""Config system: model architecture, parallelism, and input-shape configs.

Every assigned architecture is a ``ModelConfig`` in ``repro/configs/<id>.py``;
input shapes are ``ShapeConfig`` (train / prefill / decode / long-decode).
Configs are plain frozen dataclasses — no registry magic beyond
``repro.configs.get(name)``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Literal, Optional, Tuple

import jax.numpy as jnp


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared_experts: int = 0      # dense experts always active (Kimi-style)
    capacity_factor: float = 1.25
    router_dtype: str = "float32"
    # routing: scores over all n_experts (softmax, or sigmoid as in
    # DeepSeek-V3), the top_k picked and divided by their sum, times
    # routed_scale
    scoring: Literal["softmax", "sigmoid"] = "softmax"
    routed_scale: float = 1.0
    # this chip's share of an expert-parallel deployment: experts
    # first_held .. first_held + n_held - 1 (None: all of them); the router
    # keeps its n_experts outputs
    n_held: Optional[int] = None
    first_held: int = 0

    def __post_init__(self):
        if self.first_held < 0 or self.first_held + self.held > self.n_experts:
            raise ValueError(f"held experts {self.first_held} + {self.held} "
                             f"exceed the router's {self.n_experts}")

    @property
    def held(self) -> int:
        return self.n_experts if self.n_held is None else self.n_held


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 64
    head_dim: int = 64
    expand: int = 2                # d_inner = expand * d_model
    conv_width: int = 4
    chunk: int = 256               # SSD chunk length
    unroll: int = 1                # chunk-scan unroll (dry-run flop probing)
    mm_bf16: bool = False          # engine matmuls in bf16 (§Perf H8)


@dataclass(frozen=True)
class XLSTMConfig:
    slstm_every: int = 8           # one sLSTM block per this many layers
    proj_factor: float = 2.0       # mLSTM up-projection
    chunk: int = 256
    unroll: int = 1                # chunk-scan unroll (dry-run flop probing)
    mm_bf16: bool = False          # engine matmuls in bf16 (§Perf H8)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Literal["dense", "moe", "hybrid", "ssm", "vlm", "audio"]
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None          # default d_model // n_heads
    # block structure: period of layer kinds, tiled to n_layers
    block_pattern: Tuple[str, ...] = ("attn",)   # attn|mamba2|mamba2_attn|mlstm|slstm
    first_k_dense: int = 0                   # leading layers of kind "attn" before the pattern
    # attention details
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0               # chatglm 2d-RoPE = 0.5
    window: Optional[int] = None             # sliding-window attention
    # per layer "sliding_attention" (``window``) or "full_attention"; None:
    # every layer as ``window`` says
    layer_types: Optional[Tuple[str, ...]] = None
    full_rope: bool = True                   # False: no rotary on the full layers of a hybrid (NoPE)
    qk_norm: bool = False                    # chameleon
    # norms on the attention and MLP outputs before each residual add and
    # none on their inputs (EXAONE 4.0's QK-Reorder-LN)
    post_norm: bool = False
    parallel_block: bool = False             # command-r style attn ∥ mlp
    # norms / act
    norm: Literal["rmsnorm", "layernorm"] = "rmsnorm"
    act: Literal["swiglu", "gelu"] = "swiglu"
    norm_eps: float = 1e-5
    # families
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    xlstm: Optional[XLSTMConfig] = None
    enc_dec: bool = False                    # whisper
    tie_embeddings: bool = False
    logit_softcap: Optional[float] = None
    # numerics
    dtype: str = "bfloat16"                  # activation/compute dtype
    param_dtype: str = "float32"             # master params
    # notes for DESIGN/EXPERIMENTS
    sub_quadratic: bool = False              # eligible for long_500k
    notes: str = ""

    def __post_init__(self):
        # from JSON, nested groups arrive as dicts and tuples as lists
        for name, kind in (("moe", MoEConfig), ("ssm", SSMConfig),
                           ("xlstm", XLSTMConfig)):
            if isinstance(getattr(self, name), dict):
                object.__setattr__(self, name, kind(**getattr(self, name)))
        for name in ("block_pattern", "layer_types"):
            if isinstance(getattr(self, name), list):
                object.__setattr__(self, name, tuple(getattr(self, name)))
        if self.layer_types is not None and (
                len(self.layer_types) != self.n_layers
                or not set(self.layer_types) <= {"sliding_attention",
                                                 "full_attention"}):
            raise ValueError(f"{self.name}: layer_types must give "
                             f"'sliding_attention' or 'full_attention' for "
                             f"each of the {self.n_layers} layers")

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    def _layer(self, i: int) -> Tuple[str, Optional[int]]:
        """Layer ``i``'s block kind and attention window."""
        k = self.first_k_dense
        kind = "attn" if i < k else \
            self.block_pattern[(i - k) % len(self.block_pattern)]
        full = self.layer_types is not None and \
            self.layer_types[i] == "full_attention"
        return kind, None if full else self.window

    def _period_len(self) -> int:
        """The layers the scan's body holds: the shortest run of layers
        that repeats to ``n_layers`` (a multiple of the pattern's length
        unless leading dense layers break it; the whole stack at worst)."""
        n = self.n_layers
        step = 1 if self.first_k_dense else len(self.block_pattern)
        spec = [self._layer(i) for i in range(n)]
        for p in range(step, n + 1, step):
            if n % p == 0 and all(spec[i] == spec[i % p] for i in range(n)):
                return p
        raise AssertionError((self.name, self.n_layers, self.block_pattern))

    @property
    def period(self) -> Tuple[str, ...]:
        return tuple(self._layer(i)[0] for i in range(self._period_len()))

    @property
    def period_cfgs(self) -> Tuple["ModelConfig", ...]:
        """Per position of the period, the config its attention runs with:
        the layer's own window, and no rotary on a full layer of a hybrid
        without full-layer rotary."""
        out = []
        for i in range(self._period_len()):
            win = self._layer(i)[1]
            if win == self.window:
                out.append(self)
            else:
                out.append(self.replace(window=win, rope_fraction=(
                    self.rope_fraction if self.full_rope else 0.0)))
        return tuple(out)

    @property
    def n_periods(self) -> int:
        return self.n_layers // self._period_len()

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---- parameter counting (drives MODEL_FLOPS and memory estimates) ----
    def param_counts(self) -> dict:
        d, hd = self.d_model, self.hd
        counts: dict = {}
        counts["embed"] = self.vocab * d
        counts["unembed"] = 0 if self.tie_embeddings else self.vocab * d
        per_kind = {}
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
        mlp_mult = 3 if self.act == "swiglu" else 2
        per_kind["attn"] = attn + mlp_mult * d * self.d_ff + 2 * d
        if self.moe:
            e = self.moe
            experts = e.held * mlp_mult * d * e.d_ff_expert
            shared = e.n_shared_experts * mlp_mult * d * e.d_ff_expert
            router = d * e.n_experts
            per_kind["attn_moe"] = attn + experts + shared + router + 2 * d
        if self.ssm:
            s = self.ssm
            d_in = s.expand * d
            nh = d_in // s.head_dim
            per_kind["mamba2"] = (d * (2 * d_in + 2 * s.d_state + nh)
                                  + s.conv_width * (d_in + 2 * s.d_state)
                                  + 2 * nh + d_in * d + 2 * d)
            per_kind["mamba2_attn"] = per_kind["mamba2"]  # shared attn counted once below
        if self.xlstm:
            f = self.xlstm
            d_in = int(f.proj_factor * d)
            per_kind["mlstm"] = d * 2 * d_in + 3 * d_in * d_in // 1 + d_in * d + 2 * d
            per_kind["slstm"] = 4 * 2 * d * d + d * d + 2 * d
        total = counts["embed"] + counts["unembed"]
        for kind in self.period:
            base = kind if kind in per_kind else "attn"
            total += per_kind[base] * self.n_periods
        if "mamba2_attn" in self.block_pattern:
            total += attn + mlp_mult * d * self.d_ff  # one shared block
        counts["total"] = total
        # active (MoE: only top_k + shared experts per token)
        active = total
        if self.moe:
            e = self.moe
            dead = max(e.held - e.top_k, 0) * mlp_mult * d * e.d_ff_expert
            active = total - dead * self.period.count("attn_moe") * self.n_periods
        counts["active"] = active
        return counts


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: Literal["train", "prefill", "decode"]
    seq_len: int
    global_batch: int

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}


@dataclass(frozen=True)
class ParallelConfig:
    """How the model is laid out on the mesh."""
    fsdp_params: bool = True        # shard params over 'data' (ZeRO-3 style)
    fsdp_pod: bool = False          # extend param/opt sharding over 'pod'
    grad_reduce: Literal["all_reduce", "reduce_scatter_zero"] = "all_reduce"
    # ^ reduce_scatter_zero: grads reduce-scattered over the fsdp/data axes,
    #   AdamW updates only the local shard, params all-gathered (ZeRO)
    opt_state_dtype: str = "float32"   # float32|bfloat16 (compression)
    grad_dtype: str = "bfloat16"       # gradient all-reduce compression
    remat: Literal["none", "dots", "full"] = "full"
    sequence_parallel: bool = False
    use_flash_kernel: bool = False  # Pallas attention inside shard_map
    use_foopar_tp: bool = False     # algebra-based TP matmuls (paper-faithful)
    logit_chunk: Optional[int] = None  # chunked CE loss over sequence
    scan_unroll: int = 1            # layer-scan unroll (dry-run flop probing)
    moe_a2a_ep: bool = False        # token-routing EP (tokens move, not weights)
    engine_replicate: bool = False  # SSM/mLSTM engine: batch-shard only (§Perf)
    master_weights: bool = False    # bf16 params + f32 master in opt (§Perf)
    grad_barrier: bool = False      # optimization_barrier on grads (§Perf)
    manual_attention: bool = False  # manual shard_map SDPA region (§Perf)
    dp_over_model: bool = False     # pure DP: batch over BOTH axes (§Perf C7)


@dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    z_loss: float = 1e-4
    seed: int = 0
    checkpoint_every: int = 100
    checkpoint_dir: str = "/tmp/repro_ckpt"
