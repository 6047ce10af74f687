"""2D parallel matrix multiplication on the FooPar algebra: SUMMA + Cannon.

The paper's §4 family covers the 1D generic algorithm (Θ(p^{5/3})
isoefficiency) and the 3D DNS algorithm (Θ(p log p) isoefficiency but p^{1/3}
-fold replication of both operands).  This module adds the classic 2D points
of the scenario space, both expressed with the ``Grid2D`` helpers:

* ``summa_matmul``  — outer-product SUMMA (van de Geijn & Watts): L panel
  steps, each a row-broadcast of an A panel and a column-broadcast of a B
  panel, accumulated locally.  Works on rectangular q_x × q_y grids (panel
  count L = lcm(q_x, q_y)).  Memory per process: Θ(n²/p) — no replication.
* ``cannon_matmul`` — Cannon's algorithm: one skew ppermute per operand,
  then L multiply-and-ring-shift steps.  Nearest-neighbour traffic only
  (Θ(t_s + t_w m) per step vs SUMMA's log-factor broadcasts), same Θ(n²/√p)
  per-process memory.  Generalized to rectangular grids by panel windows of
  size L/q_y (A) and L/q_x (B).

Together with ``dns_matmul`` (3D) and ``core/summa_pipelined.py`` (the
overlapped/replicated tier) the repo covers the full five-point parallel
matmul scenario space.  Per process on p chips, problem size n, replication
factor c (costs from ``core/costmodel``):

  ================  =========  ==============  =======================
  variant           memory     communication   schedule / overlap
  ================  =========  ==============  =======================
  SUMMA             3n²/p      Θ(n²/√p·log √p) L tree bcasts, serial
                                               with compute
  SUMMA-pipelined   3n²/p (×2  Θ(n²/√p) ring   per-step max(t_comm,
                    panel buf) hops            t_comp) + Θ(√p) fill
  Cannon            3n²/p      Θ(n²/√p)        nearest-neighbour only,
                                               serial with compute
  Cannon-2.5D       3c·n²/p    Θ(n²/√(c·p))    q/c steps per replica
                                               layer + sum over c
  DNS (3D)          3n²/p^2/3  Θ(n²/p^{2/3}    two log-tree bcasts +
                               ·log p^{1/3})   one tree reduce
  ================  =========  ==============  =======================

The cost model picks SUMMA/Cannon when memory is tight (no replication),
the pipelined variant whenever per-step compute can hide a ring hop (large
n/√p), 2.5D when spare memory (c > 1 copies fit) can buy bandwidth, and
DNS when memory is plentiful and isoefficiency (Θ(p log p)) dominates.

All variants accept a ``local_matmul`` kernel (e.g. the Pallas MXU kernel)
exactly like ``dns_matmul`` plus a ``local_matmul_acc(a, b, c)`` fused
accumulate kernel (``kernels.ops.matmul_acc``) used by the Pallas wrappers
so the panel loop updates C in place; cost formulas live in
``costmodel.summa_matmul_cost`` / ``cannon_matmul_cost`` /
``summa_pipelined_cost`` / ``cannon_25d_cost`` and the isoefficiency
comparison in ``costmodel.isoefficiency_matmul_*``.
"""
from __future__ import annotations

import math
from typing import Callable, List

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .dseq import spmd
from .grid import Grid2D


def _skew_panels(g: Grid2D, panels: List[jax.Array], *, qx: int, qy: int,
                 L: int, operand: str) -> List[jax.Array]:
    """Cannon's alignment, at panel granularity, on a (possibly rectangular)
    grid.  After skewing, process (i, j) holds the window of panels
    ``base(i,j) + s (mod L)`` where ``base = i·L/q_x + j·L/q_y`` — exactly
    the panels its first L/len(panels) multiply steps consume.

    With one panel per process the whole window moves as one block and the
    alignment is a single ``Grid2D.skew`` ppermute (distance i·L/q_x per row
    for A, j·L/q_y per column for B).  Multi-panel windows interleave panels
    from different source processes, but for a fixed destination slot every
    source rank contributes exactly one of its local slots — so each rank
    *locally selects* the slot it must send (a dynamic index into the
    stacked window, no communication) and the whole dest slot moves as one
    merged grid-wide ppermute: n_slots ppermutes total instead of n_slots²
    partial ones with zero-fill adds.
    """
    n_slots = len(panels)
    if n_slots == 1:
        return [g.skew(panels[0], by_row=operand == "A",
                       scale=(L // qx) if operand == "A" else (L // qy))]
    stacked = jnp.stack(panels, axis=0)
    coords = g.coords[0] * qy + g.coords[1]  # linearized own rank
    out = []
    for ds in range(n_slots):
        perm = []                     # one merged permutation per dest slot
        send_slot = [-1] * (qx * qy)  # which local slot rank r contributes
        for i in range(qx):
            for j in range(qy):
                k = (i * (L // qx) + j * (L // qy) + ds) % L
                owner = k // n_slots
                src = (i, owner) if operand == "A" else (owner, j)
                src_lin = src[0] * qy + src[1]
                assert send_slot[src_lin] == -1, (
                    f"rank {src} would send twice in merged skew "
                    f"permutation (operand={operand}, dest slot {ds})")
                send_slot[src_lin] = k % n_slots
                perm.append((src_lin, i * qy + j))
        assert all(s >= 0 for s in send_slot)
        sel = jnp.asarray(send_slot)[coords]
        payload = lax.dynamic_index_in_dim(stacked, sel, 0, keepdims=False)
        out.append(lax.ppermute(payload, g.axes, perm))
    return out


def _default_mm(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def _make_mm_acc(local_matmul: Callable | None,
                 local_matmul_acc: Callable | None) -> Callable:
    """``(a, b, c) -> c + a @ b`` from whichever kernel the caller gave."""
    if local_matmul_acc is not None:
        return local_matmul_acc
    mm = local_matmul or _default_mm
    return lambda a, b, c: c + mm(a, b)


def summa_matmul(A: jax.Array, B: jax.Array, mesh: jax.sharding.Mesh,
                 *, local_matmul: Callable | None = None,
                 local_matmul_acc: Callable | None = None,
                 row_axis: str = "x", col_axis: str = "y") -> jax.Array:
    """SUMMA on a q_x × q_y process grid.

    Data layout (the static process↔data mapping, as with DNS): A and B both
    arrive block-partitioned P(x, y) — process (i, j) holds the (i, j) block
    of each.  The contraction dimension is cut into L = lcm(q_x, q_y) panels
    of width n/L; panel k of A lives in block-column k·q_y/L, panel k of B in
    block-row k·q_x/L.  For k = 0..L-1::

        a_k = bcast_row(A-panel k,  src_col = owner column of k)
        b_k = bcast_col(B-panel k,  src_row = owner row of k)
        C  += a_k @ b_k                          (local_matmul)

    Per-process cost: L row-broadcasts of (n/q_x × n/L) + L column-broadcasts
    of (n/L × n/q_y) + the same 2n³/p flops as every variant.
    """
    mm_acc = _make_mm_acc(local_matmul, local_matmul_acc)
    qx, qy = mesh.shape[row_axis], mesh.shape[col_axis]
    L = math.lcm(qx, qy)
    n_k = A.shape[1]
    assert n_k % L == 0 and A.shape[1] == B.shape[0], (A.shape, B.shape, L)

    def body(a_blk, b_blk):
        g = Grid2D(row_axis, col_axis)
        w = a_blk.shape[1] // (L // qy)          # panel width n/L
        c = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), jnp.float32)
        for k in range(L):
            a_off = (k % (L // qy)) * w
            b_off = (k % (L // qx)) * w
            a_k = g.bcast_row(a_blk[:, a_off:a_off + w], k // (L // qy))
            b_k = g.bcast_col(b_blk[b_off:b_off + w, :], k // (L // qx))
            c = mm_acc(a_k, b_k, c)
        return c

    fn = spmd(body, mesh,
              in_specs=(P(row_axis, col_axis), P(row_axis, col_axis)),
              out_specs=P(row_axis, col_axis))
    return fn(A, B)


def cannon_matmul(A: jax.Array, B: jax.Array, mesh: jax.sharding.Mesh,
                  *, local_matmul: Callable | None = None,
                  local_matmul_acc: Callable | None = None,
                  row_axis: str = "x", col_axis: str = "y") -> jax.Array:
    """Cannon's algorithm on a q_x × q_y grid (square or rectangular).

    Square grid (the classic): skew row i of A left by i and column j of B
    up by j (one ppermute each), then q steps of ``C += a @ b`` followed by
    a single ring shift of A along the row and B along the column.  All
    traffic after the skew is nearest-neighbour — no broadcast trees, which
    is Cannon's advantage over SUMMA on torus interconnects.

    Rectangular grids run the same schedule over L = lcm(q_x, q_y) panel
    steps: A's local block is a window of L/q_y panels consumed in order,
    ring-shifted one block every L/q_y steps (and symmetrically for B).
    """
    mm_acc = _make_mm_acc(local_matmul, local_matmul_acc)
    qx, qy = mesh.shape[row_axis], mesh.shape[col_axis]
    L = math.lcm(qx, qy)
    assert A.shape[1] % L == 0 and A.shape[1] == B.shape[0], (A.shape, B.shape, L)

    def body(a_blk, b_blk):
        g = Grid2D(row_axis, col_axis)
        w = a_blk.shape[1] // (L // qy)
        a_slots = [a_blk[:, s * w:(s + 1) * w] for s in range(L // qy)]
        b_slots = [b_blk[s * w:(s + 1) * w, :] for s in range(L // qx)]
        a_slots = _skew_panels(g, a_slots, qx=qx, qy=qy, L=L, operand="A")
        b_slots = _skew_panels(g, b_slots, qx=qx, qy=qy, L=L, operand="B")
        c = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), jnp.float32)
        for t in range(L):
            c = mm_acc(a_slots[t % len(a_slots)], b_slots[t % len(b_slots)], c)
            if t == L - 1:
                break
            if (t + 1) % len(a_slots) == 0:   # window exhausted: pull from j+1
                a_slots = [g.shift_row(s, -1) for s in a_slots]
            if (t + 1) % len(b_slots) == 0:
                b_slots = [g.shift_col(s, -1) for s in b_slots]
        return c

    fn = spmd(body, mesh,
              in_specs=(P(row_axis, col_axis), P(row_axis, col_axis)),
              out_specs=P(row_axis, col_axis))
    return fn(A, B)


def summa_matmul_pallas(A: jax.Array, B: jax.Array,
                        mesh: jax.sharding.Mesh) -> jax.Array:
    """SUMMA with the accumulate-in-place Pallas MXU kernel (the per-panel
    ``C += A_k B_k`` never materializes a separate product temporary)."""
    from repro.kernels.ops import matmul_acc

    return summa_matmul(A, B, mesh,
                        local_matmul_acc=matmul_acc)


def cannon_matmul_pallas(A: jax.Array, B: jax.Array,
                         mesh: jax.sharding.Mesh) -> jax.Array:
    """Cannon with the accumulate-in-place Pallas MXU kernel."""
    from repro.kernels.ops import matmul_acc

    return cannon_matmul(A, B, mesh,
                         local_matmul_acc=matmul_acc)
