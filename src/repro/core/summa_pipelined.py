"""Overlap-pipelined SUMMA and 2.5D (replicated) Cannon.

These are the two remaining points of the matmul scenario space between the
2D family (``core/summa.py``) and 3D DNS (``core/dns_matmul.py``):

* ``summa_matmul_pipelined`` — SUMMA with the per-panel log-tree broadcasts
  replaced by *double-buffered ring broadcasts* (``Grid2D.bcast_row_ring_*``
  built on ``dseq.ring_shift_d``).  The full ring transfer of panel k+1 is
  issued *before* panel k's local multiply, so the Θ(t_w·n²/(L·√p)) per-step
  transfer is independent of the multiply in the dataflow graph and the
  scheduler can hide it behind compute: per-step cost max(t_comm, t_comp)
  instead of t_comm + t_comp, plus a one-time Θ(√p) pipeline-fill latency
  (``costmodel.summa_pipelined_cost``).
* ``cannon_matmul_25d`` — Cannon with c-fold operand replication on a
  q × q × c mesh (Solomonik-Demmel 2.5D).  Each replica layer l runs q/c of
  the q Cannon steps (those with k ≡ l·q/c …), after a layer-dependent skew;
  a final sum over the replication axis assembles C.  Memory per process is
  c× the 2D algorithms' Θ(n²/p) and per-process communication drops to
  Θ(n²/√(c·p)) — the exact interpolation DNS (c = p^{1/3}) ↔ Cannon (c = 1)
  predicted by ``costmodel.cannon_25d_cost``.

Both accept ``local_matmul``/``local_matmul_acc`` kernels; the Pallas
wrappers use the accumulate-in-place MXU kernel (``kernels.ops.matmul_acc``)
so the k-step ``C += A_k B_k`` loop never materializes a separate product
temporary.
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .dseq import spmd
from .grid import Grid2D, Grid3D
from .summa import _make_mm_acc


def summa_matmul_pipelined(A: jax.Array, B: jax.Array,
                           mesh: jax.sharding.Mesh, *,
                           local_matmul: Callable | None = None,
                           local_matmul_acc: Callable | None = None,
                           row_axis: str = "x", col_axis: str = "y") -> jax.Array:
    """SUMMA with the per-panel tree broadcasts replaced by ring transfers
    (overlap pipelining).

    Same data layout and result as ``summa_matmul`` (both operands
    block-partitioned P(x, y), L = lcm(q_x, q_y) panel steps) but process
    (i, j) consumes the contraction panels in *rotated* order
    k(t) = (j·L/q_y + t) mod L — addition commutes, so every rank may
    accumulate in its own order.  That rotation removes the A broadcast
    entirely: each rank starts on its own A window (the steady state of a
    filled ring pipeline) and pulls the next window with a single
    nearest-neighbour ``shift_row`` hop — Θ(t_s + t_w m) vs the tree's
    Θ(log q (t_s + t_w m)).  The B panel for step t (its source row is the
    column-dependent owner of k(t)) travels as a double-buffered ring
    broadcast (``Grid2D.bcast_col_ring_start/next`` on ``ring_shift_d``),
    and both transfers for step t+1 are issued *before* step t's local
    multiply: the multiply consumes completed buffers while the next
    transfer is in flight, so the per-step cost is max(t_comm, t_comp)
    instead of their sum (``costmodel.summa_pipelined_cost``).
    """
    mm_acc = _make_mm_acc(local_matmul, local_matmul_acc)
    qx, qy = mesh.shape[row_axis], mesh.shape[col_axis]
    L = math.lcm(qx, qy)
    assert A.shape[1] % L == 0 and A.shape[1] == B.shape[0], (A.shape, B.shape, L)

    # step t at process column j consumes panel k = (j·wa + t) mod L; its
    # owner row and window offset are precomputed host-side so the traced
    # body does two (L,)-row gathers instead of a rem/div chain per step
    # (each traced scalar op is a dispatch thunk on every device).
    wa, wb = L // qy, L // qx
    ks = (np.arange(qy)[:, None] * wa + np.arange(L)[None, :]) % L

    def body(a_blk, b_blk):
        g = Grid2D(row_axis, col_axis)
        w = a_blk.shape[1] // wa           # panel width n_k / L
        j = lax.axis_index(g.row_axis)     # own process column
        a_slots = [a_blk[:, s * w:(s + 1) * w] for s in range(wa)]
        b_stack = jnp.stack([b_blk[s * w:(s + 1) * w, :] for s in range(wb)])
        srcs = jnp.asarray(ks // wb, jnp.int32)[j]   # (L,) owner rows
        offs = jnp.asarray(ks % wb, jnp.int32)[j]    # (L,) window offsets

        def start_b(t):
            """Issue the full ring broadcast of step t's B panel (its source
            row is this column's owner of panel k(j, t))."""
            st = g.bcast_col_ring_start(b_stack[offs[t]], srcs[t])
            for _ in range(qx - 1):
                st = g.bcast_col_ring_next(st)
            return st.value

        c = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), jnp.float32)
        b_next = start_b(0)
        for t in range(L):
            a_t, b_t = a_slots[t % wa], b_next
            if t + 1 < L:                  # double buffer: step t+1's
                b_next = start_b(t + 1)    # transfers precede this multiply
                if (t + 1) % wa == 0:      # A window exhausted: pull from j+1
                    a_slots = [g.shift_row(s, -1) for s in a_slots]
            c = mm_acc(a_t, b_t, c)
        return c

    fn = spmd(body, mesh,
              in_specs=(P(row_axis, col_axis), P(row_axis, col_axis)),
              out_specs=P(row_axis, col_axis))
    return fn(A, B)


def _skew_25d(g: Grid3D, local: jax.Array, *, q: int, c: int, steps: int,
              operand: str) -> jax.Array:
    """2.5D Cannon alignment: dest (i, j, l) receives the block its layer's
    first step consumes — A[i, (i+j+l·steps) mod q] or B[(i+j+l·steps) mod q, j]
    — as one grid-wide ppermute (the layer-dependent distance makes this
    inexpressible as per-axis shifts)."""
    perm = []
    for i in range(q):
        for j in range(q):
            for l in range(c):
                k0 = (i + j + l * steps) % q
                src = (i, k0, l) if operand == "A" else (k0, j, l)
                perm.append((src[0] * q * c + src[1] * c + src[2],
                             i * q * c + j * c + l))
    return jax.tree.map(lambda x: lax.ppermute(x, g.axes, perm), local)


def cannon_matmul_25d(A: jax.Array, B: jax.Array, mesh: jax.sharding.Mesh,
                      *, local_matmul: Callable | None = None,
                      local_matmul_acc: Callable | None = None,
                      row_axis: str = "x", col_axis: str = "y",
                      rep_axis: str = "z") -> jax.Array:
    """2.5D Cannon on a q × q × c mesh (c = extent of ``rep_axis``).

    Both operands arrive block-partitioned P(x, y) and *replicated* over the
    c replica layers (the 2.5D memory premium).  Layer l skews for Cannon
    step l·(q/c) and runs q/c multiply-and-ring-shift steps — the q-step
    Cannon schedule is split c ways across layers instead of run serially —
    then the partial C's are summed over the replication axis.  c = 1 is
    exactly ``cannon_matmul`` on a square grid; c = q is the DNS corner
    (one multiply per layer, all parallelism from the reduction).
    """
    mm_acc = _make_mm_acc(local_matmul, local_matmul_acc)
    q, qy = mesh.shape[row_axis], mesh.shape[col_axis]
    c = mesh.shape[rep_axis]
    assert q == qy, f"2.5D Cannon needs a square x,y grid, got {q}x{qy}"
    assert q % c == 0, f"replication factor {c} must divide grid side {q}"
    steps = q // c
    assert A.shape[1] % q == 0 and A.shape[1] == B.shape[0], (A.shape, B.shape, q)

    def body(a_blk, b_blk):
        g = Grid3D(row_axis, col_axis, rep_axis)
        g2 = Grid2D(row_axis, col_axis)
        a = _skew_25d(g, a_blk, q=q, c=c, steps=steps, operand="A")
        b = _skew_25d(g, b_blk, q=q, c=c, steps=steps, operand="B")
        c_part = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), jnp.float32)
        for t in range(steps):
            c_part = mm_acc(a, b, c_part)
            if t < steps - 1:
                a = g2.shift_row(a, -1)
                b = g2.shift_col(b, -1)
        return lax.psum(c_part, rep_axis)

    fn = spmd(body, mesh,
              in_specs=(P(row_axis, col_axis), P(row_axis, col_axis)),
              out_specs=P(row_axis, col_axis))
    return fn(A, B)


def summa_matmul_pipelined_pallas(A: jax.Array, B: jax.Array,
                                  mesh: jax.sharding.Mesh) -> jax.Array:
    """Pipelined SUMMA with the accumulate-in-place Pallas MXU kernel."""
    from repro.kernels.ops import matmul_acc

    return summa_matmul_pipelined(
        A, B, mesh, local_matmul_acc=matmul_acc)


def cannon_matmul_25d_pallas(A: jax.Array, B: jax.Array,
                             mesh: jax.sharding.Mesh) -> jax.Array:
    """2.5D Cannon with the accumulate-in-place Pallas MXU kernel."""
    from repro.kernels.ops import matmul_acc

    return cannon_matmul_25d(
        A, B, mesh, local_matmul_acc=matmul_acc)
