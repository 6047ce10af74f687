"""Decoder-only LM assembly: dense / MoE / hybrid(Mamba2) / xLSTM families.

Layers are grouped into the config's ``block_pattern`` period and scanned
(``lax.scan``) over ``n_periods`` stacked parameter pytrees — this keeps the
HLO size O(period) instead of O(n_layers), which matters both for compile
time and for remat policy application (one ``jax.checkpoint`` per period).

Decode threads a per-layer cache pytree through the same scan (cache as scan
xs, updated cache as scan ys).
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import xlstm as X

Params = dict


# ---------------------------------------------------------------------------
# Per-kind init / apply
# ---------------------------------------------------------------------------
def _block_init(kind: str, rng, cfg: ModelConfig) -> Params:
    ks = jax.random.split(rng, 4)
    if kind == "attn":
        return {"ln1": L.norm_init(cfg.d_model, cfg),
                "attn": L.attention_init(ks[0], cfg),
                "ln2": L.norm_init(cfg.d_model, cfg),
                "mlp": L.mlp_init(ks[1], cfg)}
    if kind == "attn_moe":
        return {"ln1": L.norm_init(cfg.d_model, cfg),
                "attn": L.attention_init(ks[0], cfg),
                "ln2": L.norm_init(cfg.d_model, cfg),
                "moe": M.moe_init(ks[1], cfg)}
    if kind in ("mamba2", "mamba2_attn"):
        return {"ln1": L.norm_init(cfg.d_model, cfg),
                "mamba": S.mamba2_init(ks[0], cfg)}
    if kind == "mlstm":
        return {"ln1": L.norm_init(cfg.d_model, cfg),
                "mlstm": X.mlstm_init(ks[0], cfg)}
    if kind == "slstm":
        return {"ln1": L.norm_init(cfg.d_model, cfg),
                "slstm": X.slstm_init(ks[0], cfg)}
    raise ValueError(kind)


def _block_apply(kind: str, p: Params, h: jax.Array, positions, cfg: ModelConfig,
                 ctx, cache: Optional[dict], cache_pos,
                 shared_attn: Optional[Params],
                 block_tables=None) -> Tuple[jax.Array, Optional[dict], jax.Array]:
    """Returns (h, new_cache, aux_loss_contribution).  ``block_tables``
    switches the attention cache to the paged page-arena view (pure
    attention patterns only — ``supports_paged``)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: Any = None

    if kind == "attn" or kind == "attn_moe":
        a_cache = cache.get("attn") if cache else None
        x1 = L.apply_norm(p["ln1"], h, cfg)
        attn_out, a_new = L.attention(p["attn"], x1, positions, cfg,
                                      cache=a_cache, cache_pos=cache_pos,
                                      block_tables=block_tables, ctx=ctx)
        if cfg.parallel_block:
            # command-r style: attn ∥ mlp read the same normed input
            if kind == "attn":
                ffn_out = L.mlp(p["mlp"], x1, cfg, ctx=ctx)
            else:
                ffn_out, probs = M.moe_ffn(p["moe"], x1, cfg, ctx)
                aux = aux + M.load_balance_loss(probs)
            h = h + attn_out + ffn_out
        else:
            h = h + attn_out
            x2 = L.apply_norm(p["ln2"], h, cfg)
            if kind == "attn":
                h = h + L.mlp(p["mlp"], x2, cfg, ctx=ctx)
            else:
                moe_out, probs = M.moe_ffn(p["moe"], x2, cfg, ctx)
                aux = aux + M.load_balance_loss(probs)
                h = h + moe_out
        new_cache = {"attn": a_new} if cache is not None else None

    elif kind in ("mamba2", "mamba2_attn"):
        m_cache = cache.get("mamba") if cache else None
        out, m_new = S.mamba2_block(p["mamba"], L.apply_norm(p["ln1"], h, cfg),
                                    cfg, cache=m_cache, ctx=ctx)
        h = h + out
        new_cache = {"mamba": m_new} if cache is not None else None
        if kind == "mamba2_attn":
            assert shared_attn is not None
            sa_cache = cache.get("shared_attn") if cache else None
            a_out, sa_new = L.attention(shared_attn["attn"],
                                        L.apply_norm(shared_attn["ln1"], h, cfg),
                                        positions, cfg, cache=sa_cache,
                                        cache_pos=cache_pos, ctx=ctx)
            h = h + a_out
            h = h + L.mlp(shared_attn["mlp"], L.apply_norm(shared_attn["ln2"], h, cfg), cfg, ctx=ctx)
            if cache is not None:
                new_cache["shared_attn"] = sa_new

    elif kind == "mlstm":
        m_cache = cache.get("mlstm") if cache else None
        out, m_new = X.mlstm_block(p["mlstm"], L.apply_norm(p["ln1"], h, cfg),
                                   cfg, cache=m_cache, ctx=ctx)
        h = h + out
        new_cache = {"mlstm": m_new} if cache is not None else None

    elif kind == "slstm":
        s_cache = cache.get("slstm") if cache else None
        out, s_new = X.slstm_block(p["slstm"], L.apply_norm(p["ln1"], h, cfg),
                                   cfg, cache=s_cache)
        h = h + out
        new_cache = {"slstm": s_new} if cache is not None else None
    else:
        raise ValueError(kind)

    return h, new_cache, aux


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames="cfg")
def init(rng, cfg: ModelConfig) -> Params:
    """Random params in ``cfg.param_dtype``, built in one compiled program
    on the default device: the periods are drawn by a ``lax.map`` straight
    into the stacked (leading ``n_periods``) leaves, so peak memory stays at
    the parameter bytes plus one period's temporaries."""
    ks = jax.random.split(rng, 4)
    period = cfg.block_pattern

    def one_period(prng):
        kr = jax.random.split(prng, len(period))
        return tuple(_block_init(k, kr[i], cfg) for i, k in enumerate(period))

    stacked = lax.map(one_period, jax.random.split(ks[0], cfg.n_periods))

    params: Params = {
        "embed": L.embed_init(ks[1], cfg),
        "layers": stacked,
        "final_norm": L.norm_init(cfg.d_model, cfg),
    }
    if "mamba2_attn" in period:
        params["shared_attn"] = {
            "ln1": L.norm_init(cfg.d_model, cfg),
            "attn": L.attention_init(ks[2], cfg),
            "ln2": L.norm_init(cfg.d_model, cfg),
            "mlp": L.mlp_init(ks[3], cfg),
        }
    return params


def init_abstract(cfg: ModelConfig) -> Params:
    """Shape-only init (no allocation) for the dry-run."""
    return jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------
def forward(params: Params, tokens: jax.Array, cfg: ModelConfig, *,
            ctx=None, remat: str = "none", unroll: int = 1,
            embeddings: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B, S) int32 (or precomputed ``embeddings`` (B, S, d) for
    stub-frontend modalities).  Returns (logits_f32 (B, S, V), aux_loss)."""
    period = cfg.block_pattern
    h = embeddings if embeddings is not None else L.embed(params["embed"], tokens, cfg)
    b, s, _ = h.shape
    positions = jnp.arange(s)
    shared_attn = params.get("shared_attn")

    def period_fn(carry, layer_p):
        h, aux = carry
        for i, kind in enumerate(period):
            h, _, a = _block_apply(kind, layer_p[i], h, positions, cfg, ctx,
                                   None, None, shared_attn)
            aux = aux + a
        if ctx is not None:
            h = _constrain(h, ctx)
        return (h, aux), None

    if remat == "full":
        period_fn = jax.checkpoint(period_fn, prevent_cse=False)
    elif remat == "dots":
        period_fn = jax.checkpoint(
            period_fn, prevent_cse=False,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    (h, aux), _ = lax.scan(period_fn, (h, jnp.zeros((), jnp.float32)),
                           params["layers"], unroll=unroll)
    h = L.apply_norm(params["final_norm"], h, cfg)
    return L.logits(params["embed"], h, cfg), aux


def _constrain(h, ctx):
    from jax.sharding import NamedSharding, PartitionSpec as P
    # §Perf H5: sequence-parallel residual — norms/elementwise run on S/tp
    # shards; GSPMD turns the row-parallel psum into reduce-scatter and the
    # column-parallel input into all-gather (Megatron-SP comm pattern).
    s_part = ctx.model_axis if getattr(ctx, "seq_parallel", False) else None
    return lax.with_sharding_constraint(
        h, NamedSharding(ctx.mesh, P(ctx.batch_axes if ctx.batch_axes else None,
                                     s_part, None)))


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Any:
    """Cache pytree stacked over periods, mirroring the layer scan."""
    period = cfg.block_pattern
    kv_len = min(max_len, cfg.window) if cfg.window else max_len

    def one(kind):
        if kind in ("attn", "attn_moe"):
            shp = (batch, kv_len, cfg.n_kv_heads, cfg.hd)
            return {"attn": (jnp.zeros(shp, dtype), jnp.zeros(shp, dtype))}
        if kind in ("mamba2", "mamba2_attn"):
            s = cfg.ssm
            d_in = s.expand * cfg.d_model
            nh = d_in // s.head_dim
            c = {"mamba": {
                "conv": jnp.zeros((batch, s.conv_width - 1, d_in + 2 * s.d_state), dtype),
                "ssm": jnp.zeros((batch, nh, s.d_state, s.head_dim), jnp.float32)}}
            if kind == "mamba2_attn":
                shp = (batch, kv_len, cfg.n_kv_heads, cfg.hd)
                c["shared_attn"] = (jnp.zeros(shp, dtype), jnp.zeros(shp, dtype))
            return c
        if kind == "mlstm":
            d_in = int(cfg.xlstm.proj_factor * cfg.d_model)
            nh, hd = cfg.n_heads, d_in // cfg.n_heads
            return {"mlstm": {"ssm": jnp.zeros((batch, nh, hd, hd + 1), jnp.float32)}}
        if kind == "slstm":
            return {"slstm": X.slstm_init_cache(batch, cfg)}
        raise ValueError(kind)

    percell = tuple(one(k) for k in period)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_periods,) + x.shape), percell)


def supports_paged(cfg: ModelConfig) -> bool:
    """True when the paged KV-cache engine can serve this config: pure
    attention patterns (pages hold K/V lines only — recurrent state has no
    per-position layout to page) with full attention (an SWA ring is itself
    a reuse scheme; it does not compose with page chains)."""
    return (not cfg.enc_dec and cfg.window is None
            and all(k in ("attn", "attn_moe") for k in cfg.block_pattern))


def init_paged_cache(cfg: ModelConfig, n_blocks: int, block: int,
                     dtype=jnp.bfloat16) -> Any:
    """Paged cache arena pytree, stacked over periods like ``init_cache``:
    per attention layer one (K, V) pair of ``(n_blocks, kv_heads, block,
    hd)`` pages shared by every request (``serving.BlockPool`` hands out the
    blocks; requests address them through block tables)."""
    if not supports_paged(cfg):
        raise NotImplementedError(
            f"paged KV cache needs a pure-attention, no-SWA pattern; got "
            f"{cfg.block_pattern} (window={cfg.window})")
    shp = (n_blocks, cfg.n_kv_heads, block, cfg.hd)
    percell = tuple({"attn": (jnp.zeros(shp, dtype), jnp.zeros(shp, dtype))}
                    for _ in cfg.block_pattern)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_periods,) + x.shape), percell)


def supports_fused_prefill(cfg: ModelConfig) -> bool:
    """True when ``prefill`` handles arbitrary (right-padded, any-length)
    prompts: pure-attention patterns, where causal masking makes end-padding
    invisible.  Recurrent kinds (mamba2/mlstm/slstm) do support ``prefill``,
    but only for unpadded prompts whose length divides into the chunk scan —
    the serving scheduler falls back to the per-token loop for them."""
    return all(k in ("attn", "attn_moe") for k in cfg.block_pattern)


def prefill(params: Params, tokens: jax.Array, cache: Any, cfg: ModelConfig, *,
            length: Optional[jax.Array] = None, ctx=None,
            unroll: int = 1) -> Tuple[jax.Array, Any]:
    """Cache-writing full-sequence forward: one fused call replaces a
    prompt-length loop of decode steps.  tokens: (B, S) int32 starting at
    position 0; the KV cache (attention) / recurrent state (SSM, xLSTM) for
    all S tokens is written in-pass.  ``length``: optional per-row true
    prompt lengths for right-padded batches — pad entries are causally
    invisible (attention patterns only; recurrent state would absorb them).
    Returns (last-position logits (B, V) f32, new_cache)."""
    period = cfg.block_pattern
    b, s = tokens.shape
    if length is not None:
        if not supports_fused_prefill(cfg):
            raise NotImplementedError(
                "padded fused prefill needs a causally-maskable pattern; "
                f"{cfg.block_pattern} carries recurrent state")
        ring = jax.tree.leaves(cache)[0].shape[2]
        if s > ring:
            # the trailing-window ring write would keep pad K/V and drop
            # real tokens; unpadded (length=None) overflow is fine
            raise NotImplementedError(
                f"right-padded prefill bucket {s} exceeds the cache ring "
                f"{ring}; cap the pad bucket at the attention window")
    h = L.embed(params["embed"], tokens, cfg)
    positions = jnp.arange(s)
    cache_pos = jnp.int32(0)
    shared_attn = params.get("shared_attn")

    def period_fn(h, xs):
        layer_p, cache_p = xs
        new_caches = []
        for i, kind in enumerate(period):
            h, nc, _ = _block_apply(kind, layer_p[i], h, positions, cfg, ctx,
                                    cache_p[i], cache_pos, shared_attn)
            new_caches.append(nc)
        return h, tuple(new_caches)

    h, new_cache = lax.scan(period_fn, h, (params["layers"], cache), unroll=unroll)
    h = L.apply_norm(params["final_norm"], h, cfg)
    if length is None:
        h_last = h[:, -1]
    else:
        idx = jnp.broadcast_to(jnp.asarray(length) - 1, (b,))
        h_last = h[jnp.arange(b), idx]
    logit = L.logits(params["embed"], h_last[:, None], cfg)[:, 0]
    return logit, new_cache


def prefill_paged(params: Params, tokens: jax.Array, cache: Any,
                  cfg: ModelConfig, *, pos0, block_tables: jax.Array,
                  length=None, ctx=None,
                  unroll: int = 1) -> Tuple[jax.Array, Any]:
    """One chunked-prefill slice: tokens (1, C) land at absolute positions
    ``pos0..pos0+C-1`` of one request's paged sequence (its pages named by
    ``block_tables`` (1, P)), writing K/V into the arena and attending
    causally over everything written so far.  ``length``: true token count
    of a right-padded final chunk.  Returns (logits at the chunk's last real
    token (1, V) f32, new_cache) — only the final chunk's logits are used
    (they seed the first generated token)."""
    period = cfg.block_pattern
    b, s = tokens.shape
    h = L.embed(params["embed"], tokens, cfg)
    positions = pos0 + jnp.arange(s)
    shared_attn = params.get("shared_attn")

    def period_fn(h, xs):
        layer_p, cache_p = xs
        new_caches = []
        for i, kind in enumerate(period):
            h, nc, _ = _block_apply(kind, layer_p[i], h, positions, cfg, ctx,
                                    cache_p[i], pos0, shared_attn,
                                    block_tables=block_tables)
            new_caches.append(nc)
        return h, tuple(new_caches)

    h, new_cache = lax.scan(period_fn, h, (params["layers"], cache), unroll=unroll)
    h = L.apply_norm(params["final_norm"], h, cfg)
    idx = (jnp.asarray(length) if length is not None else s) - 1
    h_last = h[jnp.arange(b), jnp.broadcast_to(idx, (b,))]
    return L.logits(params["embed"], h_last[:, None], cfg)[:, 0], new_cache


def decode_step(params: Params, token: jax.Array, cache: Any, pos: jax.Array,
                cfg: ModelConfig, *, ctx=None, unroll: int = 1,
                block_tables=None) -> Tuple[jax.Array, Any]:
    """One decode step.  token: (B,) int32; pos: scalar absolute position, or
    a (B,) vector of per-row positions (continuous-batching slots advance
    independently).  ``block_tables`` (B, P): paged mode — ``cache`` is the
    page arena and each row addresses its own page chain.  Returns
    (logits (B, V) f32, new_cache)."""
    period = cfg.block_pattern
    h = L.embed(params["embed"], token[:, None], cfg)       # (B, 1, d)
    positions = pos[None] if jnp.ndim(pos) == 0 else pos[:, None]
    cache_pos = pos if cfg.window is None else pos % cfg.window
    shared_attn = params.get("shared_attn")

    def period_fn(h, xs):
        layer_p, cache_p = xs
        new_caches = []
        for i, kind in enumerate(period):
            h, nc, _ = _block_apply(kind, layer_p[i], h, positions, cfg, ctx,
                                    cache_p[i], cache_pos, shared_attn,
                                    block_tables=block_tables)
            new_caches.append(nc)
        return h, tuple(new_caches)

    h, new_cache = lax.scan(period_fn, h, (params["layers"], cache), unroll=unroll)
    h = L.apply_norm(params["final_norm"], h, cfg)
    logit = L.logits(params["embed"], h, cfg)[:, 0]
    return logit, new_cache
