"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` layer).

These define the semantics the kernels must match (assert_allclose in
tests/test_kernels.py across shape/dtype sweeps).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def matmul(a: jax.Array, b: jax.Array, *, out_dtype=jnp.float32) -> jax.Array:
    """C = A @ B with f32 accumulation."""
    return jnp.matmul(a, b, preferred_element_type=jnp.float32).astype(out_dtype)


def minplus(a: jax.Array, b: jax.Array) -> jax.Array:
    """(min, +) matrix product: C[i,j] = min_k A[i,k] + B[k,j]."""
    return jnp.min(a[:, :, None] + b[None, :, :], axis=1)


def gather_pages(pages: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Dense ``(B, P*block, Hkv, hd)`` view of each request's page chain in
    an ``(N, Hkv, block, hd)`` arena; -1 table entries clamp to block 0
    (callers mask those positions)."""
    b, p = block_tables.shape
    _, hkv, blk, hd = pages.shape
    g = pages[jnp.maximum(block_tables, 0)]              # (B, P, Hkv, blk, hd)
    return g.swapaxes(2, 3).reshape(b, p * blk, hkv, hd)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array) -> jax.Array:
    """Paged decode-attention oracle: one query token per request, K/V
    gathered through the block table.

    q: (B, Hkv, rep, hd) — grouped query heads (GQA: rep = Hq // Hkv).
    k_pages, v_pages: (N, Hkv, block, hd) — the shared page arenas.
    block_tables: (B, P) int32 — request b's logical page j lives in
    physical block ``block_tables[b, j]``; -1 marks an unallocated tail
    entry (its keys are masked, the gather clamps the index).
    lengths: (B,) int32 — valid tokens per request (key positions
    >= lengths[b] masked, incl. the partially-filled last page).

    Dtype discipline mirrors ``models.layers._sdpa`` exactly (f32 scores and
    softmax, probabilities cast back to q.dtype for the PV contraction) so
    the paged decode engine's greedy tokens match the end-aligned engine's.
    """
    hd = q.shape[-1]
    k = gather_pages(k_pages, block_tables)              # (B, K, Hkv, hd)
    v = gather_pages(v_pages, block_tables)
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bgrd,bkgd->bgrk", q * scale, k,
                   preferred_element_type=jnp.float32)
    kpos = jnp.arange(k.shape[1])
    mask = kpos[None, :] < lengths[:, None]              # (B, K)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    probs = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrk,bkgd->bgrd", probs, v,
                      preferred_element_type=q.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None) -> jax.Array:
    """Multi-head attention oracle.

    q: (B, Hq, Lq, D); k, v: (B, Hkv, Lk, D) with Hq % Hkv == 0 (GQA).
    ``window``: sliding-window size (keys j with i_abs - j >= window masked);
    query position i is aligned to the *end* of the key sequence (prefill:
    Lq == Lk; decode: Lq == 1 attending to a cache of Lk).
    """
    b, hq, lq, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    kk = jnp.repeat(k, rep, axis=1)
    vv = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32))
    s *= scale if scale is not None else (1.0 / jnp.sqrt(d))
    lk = k.shape[2]
    qpos = jnp.arange(lq) + (lk - lq)          # query absolute positions
    kpos = jnp.arange(lk)
    mask = jnp.ones((lq, lk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= qpos[:, None] - kpos[None, :] < window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
    return out.astype(q.dtype)
