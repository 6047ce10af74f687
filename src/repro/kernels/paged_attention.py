"""Paged decode-attention Pallas kernel (the serving subsystem's hot loop).

One query token per request attends to a KV cache scattered across
fixed-size pages of a shared arena; the request's *block table* names its
pages.  The table and the lengths are scalar-prefetched
(``pltpu.PrefetchScalarGridSpec``), and the arenas stay in HBM
(``memory_space=ANY``): the kernel copies the pages it needs itself.

The arenas are laid out ``(N, Hkv, block, hd)``, so one page of one arena,
all KV heads of it, is a single contiguous ``(Hkv, block, hd)`` slab.  The
grid is one program per request (slot) with the KV heads folded in: each
page is one DMA per arena, never ``Hkv`` smaller ones.

Inside a program a ``fori_loop`` walks the row's *live* pages only, in
groups of ``G`` pages: the walk starts at the page of the row's first key
position (0, or a sliding window's start) and ends at the row's length or
at its first unallocated (-1) table entry, whichever comes first, so a
parked row (table all -1) walks nothing and writes zeros.  Each group's
pages are copied into a VMEM buffer of ``G`` pages with manual async
copies, double-buffered: group ``i + 1`` is in flight while group ``i`` is
computed.  A live page
costs its two DMAs (K and V) and its share of one loop step; a dead table
entry costs nothing but the scalar test that ends the walk.  ``G`` follows
from the shapes (``_pages_per_group``), not from a knob.

Per group and KV head, the ``rep`` grouped query heads take f32 scores
against the group's ``G * block`` keys; the online-softmax statistics
(m, l) and the f32 output accumulator live in VMEM scratch across groups.
Key positions at or past the walk's end, which includes the tail of the
partially-filled last page, and below the row's first key position are
masked per position.

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

# keys one walk step stages: the step's fixed cost (loop, semaphore waits,
# softmax statistics) must be small against its pages; on a v5e at 16-token
# pages, 32 pages a step took 42-47% less time than 4 in both head layouts
# of the benchmark cells (2 KV heads of 16 queries, 8 of 8), and 64 gained
# on one layout what it lost on the other
GROUP_TOKENS = 512
BUFFER_BYTES = 4 * 1024 * 1024  # VMEM for K and V, both buffers


def _pages_per_group(page_bytes: int, block: int, pages: int) -> int:
    """Pages a walk step stages per arena: ``GROUP_TOKENS`` keys, within
    ``BUFFER_BYTES`` for two double-buffered arenas, and never more than
    the table holds."""
    g = min(pl.cdiv(GROUP_TOKENS, block), BUFFER_BYTES // (4 * page_bytes))
    return max(1, min(g, pages))


def _paged_attn_kernel(tbl_ref, len_ref, start_ref, q_ref, k_hbm, v_hbm, o_ref,
                       k_buf, v_buf, sems, m_ref, l_ref, acc_ref, *,
                       pages: int, group: int, block: int, scale: float):
    b = pl.program_id(0)
    hkv = k_buf.shape[2]
    span = group * block

    @pl.when(b == 0)
    def _zero():
        # a group's pages past the walk's end are never copied; their keys
        # are masked, but 0 * (uninitialised V) could still be NaN
        v_buf[...] = jnp.zeros_like(v_buf)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    # the walk: the row's pages from its first key's up to its length or
    # its first -1 entry
    length, start = len_ref[b], start_ref[b]
    want = jnp.minimum(pl.cdiv(length, block), pages)
    live = jax.lax.while_loop(
        lambda j: (j < want) & (tbl_ref[b, jnp.minimum(j, pages - 1)] >= 0),
        lambda j: j + 1, 0)
    limit = jnp.minimum(length, live * block)
    first = jnp.minimum(start // block, live)
    n_groups = pl.cdiv(live - first, group)

    def copies(g, slot, fn):
        for i in range(group):
            j = first + g * group + i

            @pl.when(j < live)
            def _page():
                page = tbl_ref[b, j]
                fn(pltpu.make_async_copy(k_hbm.at[page], k_buf.at[slot, i],
                                         sems.at[0, slot]))
                fn(pltpu.make_async_copy(v_hbm.at[page], v_buf.at[slot, i],
                                         sems.at[1, slot]))

    @pl.when(n_groups > 0)
    def _first():
        copies(0, 0, lambda c: c.start())

    q = [q_ref[0, h].astype(jnp.float32) * scale for h in range(hkv)]

    def step(g, carry):
        slot = g % 2

        @pl.when(g + 1 < n_groups)
        def _next():
            copies(g + 1, 1 - slot, lambda c: c.start())

        copies(g, slot, lambda c: c.wait())
        kpos = (first * block + g * span + jax.lax.broadcasted_iota(
            jnp.int32, (q[0].shape[0], span), 1))
        valid = (kpos < limit) & (kpos >= start)
        for h in range(hkv):
            k = k_buf[slot, :, h].reshape(span, -1).astype(jnp.float32)
            v = v_buf[slot, :, h].reshape(span, -1).astype(jnp.float32)
            s = jax.lax.dot_general(q[h], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s, NEG_INF)              # (rep, G * block)
            m_prev, l_prev = m_ref[h], l_ref[h]           # (rep, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_groups, step, 0)
    l = l_ref[...]
    safe = jnp.where(l == 0.0, 1.0, l)       # nothing walked (parked row)
    o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def paged_attention_pallas(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, starts: jax.Array | None = None,
                           *, scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """Layouts as ``ref.paged_attention``: q (B, Hkv, rep, hd); arenas
    (N, Hkv, block, hd); block_tables (B, P) int32 (-1 = unallocated);
    lengths (B,) int32 valid tokens; starts (B,) int32 first key positions
    (None: 0)."""
    b, hkv, rep, hd = q.shape
    if starts is None:
        starts = jnp.zeros_like(lengths)
    n, hkv2, blk, hd2 = k_pages.shape
    assert (hkv, hd) == (hkv2, hd2), (q.shape, k_pages.shape)
    pages = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    page_bytes = hkv * blk * hd * k_pages.dtype.itemsize
    group = _pages_per_group(page_bytes, blk, pages)

    kernel = functools.partial(_paged_attn_kernel, pages=pages, group=group,
                               block=blk, scale=scale)
    buf = pltpu.VMEM((2, group, hkv, blk, hd), k_pages.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # (block_tables, lengths, starts)
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hkv, rep, hd), lambda bb, *_: (bb, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # arenas stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, rep, hd),
                               lambda bb, *_: (bb, 0, 0, 0)),
        scratch_shapes=[
            buf, buf,
            pltpu.SemaphoreType.DMA((2, 2)),        # (K | V, buffer)
            pltpu.VMEM((hkv, rep, 1), jnp.float32),
            pltpu.VMEM((hkv, rep, 1), jnp.float32),
            pltpu.VMEM((hkv, rep, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # sequential: buffers zeroed once, at the first program
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(block_tables, lengths, starts, q, k_pages, v_pages)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array,
                    starts: jax.Array | None = None) -> jax.Array:
    """Paged decode attention, the model decode path's entry: the Pallas
    kernel on TPU, the jnp reference elsewhere."""
    if jax.default_backend() == "tpu":
        return paged_attention_pallas(q, k_pages, v_pages, block_tables,
                                      lengths, starts)
    return ref.paged_attention(q, k_pages, v_pages, block_tables, lengths,
                               starts)
