"""Paged decode-attention Pallas kernel (the serving subsystem's hot loop).

One query token per request attends to a KV cache scattered across
fixed-size pages of a shared arena; the request's *block table* names its
pages.  The kernel gathers K/V blocks **through the table** with scalar
prefetch (``pltpu.PrefetchScalarGridSpec``): the table row is available
before the body runs, so each page's BlockSpec ``index_map`` picks the
physical arena block to DMA — the gather costs no extra kernel pass.

The arenas are laid out ``(N, Hkv, block, hd)``, so one (page, kv-head)
tile is a whole ``(block, hd)`` slab: the last two dims of every staged
block are the array's own, which the TPU's tiling accepts for any page
size and head count.

Grid (B, Hkv, P): each (request, kv-head) pair owns a run of the innermost
page dimension; the online-softmax statistics (m, l) and the f32 output
accumulator for its ``rep`` grouped query heads persist in VMEM scratch
across pages (the same revisiting pattern as ``flash_attention.py``).
Pages past the request's valid length — and unallocated (-1) table entries
— are skipped whole with ``pl.when`` (the TPU grid is sequential per core,
so the skip saves real time: a request occupying 3 of P=64 table slots pays
for 3 page reads, not 64); the partially-filled last page is masked
per-position.

``paged_attention`` is the public entry: on TPU it lowers the kernel (a
lowering failure surfaces), elsewhere it runs the pure-jnp reference in
``ref.py`` — the reference rather than interpret-mode Pallas, because the
serving engine calls this once per decode tick and interpret-mode
evaluation is a correctness harness, not a serving path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

NEG_INF = -1e30


def _paged_attn_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, pages: int, block: int,
                       scale: float):
    b = pl.program_id(0)
    pg = pl.program_id(2)

    @pl.when(pg == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    base = pg * block
    # whole-page skip: past the valid length, or an unallocated table entry
    live = (base < length) & (tbl_ref[b, pg] >= 0)

    @pl.when(live)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (rep, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (block, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (rep, block)
        kpos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)          # partial last page

        m_prev, l_prev = m_ref[...], l_ref[...]           # (rep, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(pg == pages - 1)
    def _store():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)   # all pages dead (parked row)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def paged_attention_pallas(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *, scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """Layouts as ``ref.paged_attention``: q (B, Hkv, rep, hd); arenas
    (N, Hkv, block, hd); block_tables (B, P) int32 (-1 = unallocated);
    lengths (B,) int32 valid tokens."""
    b, hkv, rep, hd = q.shape
    n, hkv2, blk, hd2 = k_pages.shape
    assert (hkv, hd) == (hkv2, hd2), (q.shape, k_pages.shape)
    pages = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)

    kernel = functools.partial(_paged_attn_kernel, pages=pages, block=blk,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # (block_tables, lengths)
        grid=(b, hkv, pages),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), lambda bb, h, p, tbl, lens: (bb, h, 0, 0)),
            # the page gather: the arena block to stage is *named by the
            # prefetched table*, clamped so dead (-1) entries stay in range
            # (their page is skipped in the body)
            pl.BlockSpec((1, 1, blk, hd),
                         lambda bb, h, p, tbl, lens: (jnp.maximum(tbl[bb, p], 0), h, 0, 0)),
            pl.BlockSpec((1, 1, blk, hd),
                         lambda bb, h, p, tbl, lens: (jnp.maximum(tbl[bb, p], 0), h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd), lambda bb, h, p, tbl, lens: (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_tables, lengths, q, k_pages, v_pages)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array) -> jax.Array:
    """Paged decode attention, the model decode path's entry: the Pallas
    kernel on TPU, the jnp reference elsewhere."""
    if jax.default_backend() == "tpu":
        return paged_attention_pallas(q, k_pages, v_pages, block_tables,
                                      lengths)
    return ref.paged_attention(q, k_pages, v_pages, block_tables, lengths)
