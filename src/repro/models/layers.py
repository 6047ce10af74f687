"""Shared transformer layers: norms, RoPE, GQA attention (+SWA, qk-norm,
2d-RoPE), MLPs.  Pure functions over dict-pytree params.

Conventions:
  * params are dicts of jnp arrays; init fns take an ``rng`` and a
    ``ModelConfig`` and return the dict (use ``jax.eval_shape`` for abstract
    init in the dry-run).
  * activations run in ``cfg.dtype`` (bf16), matmul accumulation and
    softmax/norm statistics in f32.
  * decode: ``cache`` is (k, v) of shape (B, L, Hkv, hd); the new token is
    written at ``pos`` (ring position for sliding windows) before attending.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.config import ModelConfig

Params = dict


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def dense_init(rng, d_in: int, d_out: int, cfg: ModelConfig, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(rng, (d_in, d_out), _pdtype(cfg)) * scale)


def dense(x: jax.Array, w: jax.Array, cfg: ModelConfig) -> jax.Array:
    # bf16 output directly: the MXU accumulates in f32 internally; keeping the
    # HLO result bf16 lets GSPMD run the TP all-reduces in bf16 (2× wire).
    dt = _dtype(cfg)
    return jnp.matmul(x.astype(dt), w.astype(dt), preferred_element_type=dt)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def norm_init(d: int, cfg: ModelConfig) -> Params:
    p = {"scale": jnp.ones((d,), _pdtype(cfg))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), _pdtype(cfg))
    return p


def apply_norm(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        xf = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + cfg.norm_eps)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
        xf = (xf - mu) * lax.rsqrt(var + cfg.norm_eps)
    out = xf * p["scale"].astype(jnp.float32)
    if "bias" in p:
        out = out + p["bias"].astype(jnp.float32)
    return out.astype(_dtype(cfg))


# ---------------------------------------------------------------------------
# RoPE (standard + fractional "2d" chatglm variant)
# ---------------------------------------------------------------------------
def rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: (..., S, H, hd); positions: (S,) or (B, S) absolute positions."""
    hd = x.shape[-1]
    rot = int(hd * cfg.rope_fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    # broadcast over head dim: (..., S, 1, half)
    cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    x_rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([x_rot.astype(x.dtype), x_pass], axis=-1)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------
def attention_init(rng, cfg: ModelConfig) -> Params:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], d, hq * hd, cfg),
        "wk": dense_init(ks[1], d, hkv * hd, cfg),
        "wv": dense_init(ks[2], d, hkv * hd, cfg),
        "wo": dense_init(ks[3], hq * hd, d, cfg),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((hd,), _pdtype(cfg))}
        p["k_norm"] = {"scale": jnp.ones((hd,), _pdtype(cfg))}
    return p


def _qk_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    xf = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


def _sdpa(q, k, v, *, causal: bool, window: Optional[int], q_offset: int | jax.Array,
          kv_len_valid=None) -> jax.Array:
    """Grouped SDPA.  q: (B, Lq, Hkv, rep, hd); k, v: (B, Lk, Hkv, hd).
    ``q_offset``: absolute position of q[0] minus first key position —
    scalar, or (B,) for per-row positions (continuous-batching decode).
    ``kv_len_valid``: number of valid cache slots (decode with a partially
    filled cache) — scalar or (B,)."""
    b, lq, hkv, rep, hd = q.shape
    lk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    # bf16 operands, f32 accumulation (MXU-native); stats in f32
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q * scale, k,
                   preferred_element_type=jnp.float32)
    q_off = jnp.asarray(q_offset)
    # (Lq,) for scalar offsets, (B, Lq) for per-row offsets
    qpos = jnp.arange(lq) + (q_off[..., None] if q_off.ndim else q_off)
    kpos = jnp.arange(lk)
    mask = jnp.ones(qpos.shape + (lk,), bool)
    if causal:
        mask &= kpos <= qpos[..., None]
    if window is not None:
        mask &= qpos[..., None] - kpos < window
    if kv_len_valid is not None:
        kvv = jnp.asarray(kv_len_valid)
        mask = mask & (kpos < (kvv[..., None, None] if kvv.ndim else kvv))
    if mask.ndim == 3:                      # per-row mask: (B, 1, 1, Lq, Lk)
        mask = mask[:, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p, v,
                     preferred_element_type=q.dtype)
    return out


def _cstr(x, ctx, parts):
    """with_sharding_constraint if a MeshCtx is given (else no-op)."""
    if ctx is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    return lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, P(*parts)))


def _sdpa_manual(q, k, v, ctx, *, causal, window):
    """Sequence-sharded attention with a manual shard_map over ``model``:
    each shard holds S/p query rows (full heads) and the full (GQA-small)
    K/V; the causal mask offsets by the shard's global row base."""
    import jax
    from jax.sharding import PartitionSpec as P
    M = ctx.model_axis
    s_loc = q.shape[1] // ctx.model_size
    assert q.shape[1] % ctx.model_size == 0

    def body(ql, kl, vl):
        off = lax.axis_index(M) * s_loc
        return _sdpa(ql, kl, vl, causal=causal, window=window, q_offset=off)

    # fully manual over the mesh (axis_index inside a partial-manual region
    # lowers to PartitionId, which SPMD partitioning rejects): batch over the
    # batch axes, sequence over model.
    bat = ctx.batch_axes if ctx.batch_axes else None
    from repro.core.compat import shard_map as _shard_map
    return _shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(bat, M, None, None, None), P(bat, None, None, None),
                  P(bat, None, None, None)),
        out_specs=P(bat, M, None, None, None),
        check=False)(q, k, v)


def attention(p: Params, x: jax.Array, positions: jax.Array, cfg: ModelConfig, *,
              causal: bool = True,
              cache: Optional[Tuple[jax.Array, jax.Array]] = None,
              cache_pos: Optional[jax.Array] = None,
              xattn_kv: Optional[jax.Array] = None,
              block_tables: Optional[jax.Array] = None,
              ctx=None,
              ) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """Self- (or cross-) attention.

    Train/prefill: ``cache=None`` — full causal attention over x.
    Decode: ``cache=(k, v)`` of length L; the new token's k/v are written at
    ``cache_pos`` (already ring-reduced for SWA), then q attends to the cache.
    Paged decode/prefill: ``block_tables`` given — ``cache`` is the shared
    page *arena* ``(n_blocks, Hkv, block, hd)`` and each request reads/writes
    through its block-table row (the page view; ``serving/kvcache.py`` owns
    the host-side allocation).
    Cross-attention (whisper): ``xattn_kv`` is the encoder output; keys/values
    are computed from it, no cache/causality.

    Distribution (Ulysses-style, DESIGN.md §3): heads are never sharded (GQA
    head counts rarely divide TP); instead the attention einsum region is
    *sequence-sharded* over ``model`` — GSPMD reshards proj outputs with an
    all-to-all (Table-1 ``allToAllD``), each shard computes full-head
    attention on S/p query rows against replicated (small, GQA) K/V, and the
    output all-to-alls back to feature sharding for the row-parallel wo.
    Decode shards the *cache length* over ``model`` instead (softmax stats
    combine with tiny psums).
    """
    b, s, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    rep = hq // hkv
    B = ctx.batch_axes if (ctx and ctx.batch_axes) else None
    M = ctx.model_axis if ctx else None
    if ctx is not None and getattr(ctx, "dp_over_model", False):
        M = None  # pure DP: attention is local per batch shard

    q = dense(x, p["wq"], cfg).reshape(b, s, hkv, rep, hd)
    kv_src = xattn_kv if xattn_kv is not None else x
    k = dense(kv_src, p["wk"], cfg).reshape(b, -1, hkv, hd)
    v = dense(kv_src, p["wv"], cfg).reshape(b, -1, hkv, hd)

    if cfg.qk_norm:
        q = _qk_norm(q, p["q_norm"]["scale"], cfg.norm_eps)
        k = _qk_norm(k, p["k_norm"]["scale"], cfg.norm_eps)

    if xattn_kv is None:
        q = rope(q.reshape(b, s, hkv * rep, hd), positions, cfg).reshape(b, s, hkv, rep, hd)
        k = rope(k, positions, cfg)

    new_cache = None
    if cache is not None and block_tables is not None:
        # ---- paged cache: requests read/write the shared page arena
        # through their block-table rows (chains of fixed-size pages replace
        # the per-slot end-aligned row, so prompt+gen is bounded by pool
        # capacity, not slot length).  SWA rings and paging don't compose.
        assert cfg.window is None, "paged attention needs full (no-SWA) attention"
        ck, cv = cache                    # (n_blocks, Hkv, block, hd) arenas
        n_blocks, blk = ck.shape[0], ck.shape[2]
        if jnp.ndim(cache_pos) == 1:
            # decode: each request writes its token at page pos//block,
            # offset pos%block of its own chain; rows whose table entry is
            # -1 (parked/free slots) map OOB and the write drops
            pg, off = cache_pos // blk, cache_pos % blk
            entry = jnp.take_along_axis(block_tables, pg[:, None], axis=1)[:, 0]
            phys = jnp.where(entry >= 0, entry, n_blocks)
            ck = ck.at[phys, :, off].set(k[:, 0].astype(ck.dtype), mode="drop")
            cv = cv.at[phys, :, off].set(v[:, 0].astype(cv.dtype), mode="drop")
            from repro.kernels.paged_attention import paged_attention
            out = paged_attention(q[:, 0], ck, cv, block_tables,
                                  cache_pos + 1)[:, None]
        else:
            # chunked prefill (one request, B=1): the chunk's tokens land at
            # absolute positions cache_pos..cache_pos+s-1 through the table,
            # then attend causally against the gathered page view.  Writes
            # from right-pad tokens are harmless: every position is
            # re-written by its real token (next chunk / decode step) before
            # any query ever attends to it, and pad queries' outputs are
            # dropped by the length pick.
            assert b == 1, "chunked prefill runs one request per call"
            tpos = cache_pos + jnp.arange(s)
            pg, off = tpos // blk, tpos % blk
            # pad-token positions can run past the table width; an unguarded
            # gather would CLAMP to the last (live!) entry and scatter pad
            # K/V over real tokens — route them OOB so the write drops
            n_pages = block_tables.shape[1]
            entry = jnp.where(pg < n_pages,
                              block_tables[0, jnp.minimum(pg, n_pages - 1)],
                              -1)
            phys = jnp.where(entry >= 0, entry, n_blocks)
            ck = ck.at[phys, :, off].set(k[0].astype(ck.dtype), mode="drop")
            cv = cv.at[phys, :, off].set(v[0].astype(cv.dtype), mode="drop")
            from repro.kernels.ref import gather_pages
            out = _sdpa(q, gather_pages(ck, block_tables),
                        gather_pages(cv, block_tables),
                        causal=True, window=None, q_offset=cache_pos)
        new_cache = (ck, cv)
    elif cache is not None:
        ck, cv = cache  # (B, L, Hkv, hd), L sharded over model
        lk = ck.shape[1]
        if jnp.ndim(cache_pos) == 1:
            # per-row positions (continuous-batching decode, s == 1): scatter
            # each row's token at its own slot; OOB rows (parked slots) drop
            bidx = jnp.arange(b)
            ck = ck.at[bidx, cache_pos].set(k[:, 0].astype(ck.dtype), mode="drop")
            cv = cv.at[bidx, cache_pos].set(v[:, 0].astype(cv.dtype), mode="drop")
        elif s > lk:
            # fused SWA prefill, prompt longer than the ring: keep the last
            # lk tokens at their ring slots (token j -> slot j % lk)
            slots = np.arange(s - lk, s) % lk
            ck = ck.at[:, slots].set(k[:, s - lk:].astype(ck.dtype))
            cv = cv.at[:, slots].set(v[:, s - lk:].astype(cv.dtype))
        else:
            ck = lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_pos, axis=1)
            cv = lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_pos, axis=1)
        ck = _cstr(ck, ctx, (B, M, None, None))
        cv = _cstr(cv, ctx, (B, M, None, None))
        new_cache = (ck, cv)
        q = _cstr(q, ctx, (B, None, None, None, None))
        if s > lk:
            # prefill longer than the ring: attend the full in-flight k/v
            # (the cache holds only the trailing window)
            out = _sdpa(q, k, v, causal=True, window=cfg.window, q_offset=0)
        elif cfg.window is not None and lk == cfg.window and s == 1:
            # ring cache decode: slot validity from the absolute position —
            # before the first wrap only pos+1 slots hold real tokens (the
            # untouched zero-k/v slots would otherwise soak up softmax mass)
            valid = jnp.minimum(positions[..., -1] + 1, lk)
            out = _sdpa(q, ck, cv, causal=False, window=None, q_offset=0,
                        kv_len_valid=valid)
        else:
            # end-aligned: query position == cache_pos
            out = _sdpa(q, ck, cv, causal=True, window=cfg.window,
                        q_offset=cache_pos)
    elif ctx is not None and getattr(ctx, "manual_attention", False) and s > 1 \
            and not getattr(ctx, "dp_over_model", False):
        # §Perf A8: the einsum region as a *manual* shard_map over model on
        # the S dim — GSPMD cannot re-shard inside (kills the involuntary
        # q-replication all-gathers the constraint-based path suffers)
        out = _sdpa_manual(q, k, v, ctx, causal=causal and xattn_kv is None,
                           window=cfg.window)
    else:
        # sequence-sharded einsum region (all-to-all in, all-to-all out)
        q = _cstr(q, ctx, (B, M, None, None, None))
        k = _cstr(k, ctx, (B, None, None, None))
        v = _cstr(v, ctx, (B, None, None, None))
        out = _sdpa(q, k, v, causal=causal and xattn_kv is None,
                    window=cfg.window, q_offset=0)
        out = _cstr(out, ctx, (B, M, None, None, None))

    out = out.reshape(b, s, hq * hd)
    out = _cstr(out, ctx, (B, None, M))
    return dense(out, p["wo"], cfg), new_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
def mlp_init(rng, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    if cfg.act == "swiglu":
        return {"w_gate": dense_init(ks[0], d, ff, cfg),
                "w_up": dense_init(ks[1], d, ff, cfg),
                "w_down": dense_init(ks[2], ff, d, cfg)}
    return {"w_up": dense_init(ks[0], d, ff, cfg),
            "w_down": dense_init(ks[1], ff, d, cfg)}


def mlp(p: Params, x: jax.Array, cfg: ModelConfig, ctx=None) -> jax.Array:
    if ctx is not None and getattr(ctx, "foopar_tp", False):
        return _mlp_foopar(p, x, cfg, ctx)
    if "w_gate" in p:
        g = dense(x, p["w_gate"], cfg)
        u = dense(x, p["w_up"], cfg)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype) * u
    else:
        h = jax.nn.gelu(dense(x, p["w_up"], cfg).astype(jnp.float32)).astype(_dtype(cfg))
    return dense(h, p["w_down"], cfg)


def _mlp_foopar(p: Params, x: jax.Array, cfg: ModelConfig, ctx) -> jax.Array:
    """Paper-faithful TP MLP: the FooPar algebra's column-parallel mapD for
    the up/gate projections and zipWithD∘reduceD('sum') for the down
    projection (core/tensor_ops.py) — same math as the pjit path; §Perf
    compares the compiled collective schedules."""
    from repro.core.tensor_ops import foopar_matmul_col, foopar_matmul_row
    dt = _dtype(cfg)
    mesh, ax = ctx.mesh, ctx.model_axis
    xx = x.astype(dt)
    if "w_gate" in p:
        g = foopar_matmul_col(xx, p["w_gate"].astype(dt), mesh=mesh, axis=ax,
                              preferred_element_type=dt)
        u = foopar_matmul_col(xx, p["w_up"].astype(dt), mesh=mesh, axis=ax,
                              preferred_element_type=dt)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
    else:
        h = jax.nn.gelu(foopar_matmul_col(xx, p["w_up"].astype(dt), mesh=mesh,
                                          axis=ax, preferred_element_type=dt)
                        .astype(jnp.float32)).astype(dt)
    return foopar_matmul_row(h, p["w_down"].astype(dt), mesh=mesh, axis=ax,
                             preferred_element_type=dt)


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------
def embed_init(rng, cfg: ModelConfig) -> Params:
    p = {"embedding": jax.random.normal(rng, (cfg.vocab, cfg.d_model), _pdtype(cfg)) * 0.02}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(jax.random.fold_in(rng, 1), cfg.d_model, cfg.vocab, cfg)
    return p


def embed(p: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0).astype(_dtype(cfg))


def logits(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    w = p["embedding"].T if cfg.tie_embeddings else p["unembed"]
    out = jnp.matmul(x.astype(_dtype(cfg)), w.astype(_dtype(cfg)),
                     preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        out = c * jnp.tanh(out / c)
    return out  # f32
