"""Parallel matrix-matrix multiplication (paper §4) on the FooPar algebra.

Three implementations:

* ``generic_matmul``  — paper Algorithm 1: the q² reductions are emulated by a
  sequential Python for-loop (the paper's point: this costs Θ(p^{2/3}) nops and
  caps scalability at W ∈ Θ(p^{5/3})).
* ``dns_matmul``      — paper Algorithm 2: Grid3D abstraction; communication
  pattern of the DNS algorithm, isoefficiency Θ(n³ + p log p).
* ``dns_matmul_pallas`` — Algorithm 2 with the local block multiply done by the
  Pallas MXU kernel (the paper's JBLAS/MKL layer).

All operate on logically (n, n) matrices decomposed into q×q blocks.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .dseq import DSeq, apply_d, spmd
from .grid import Grid3D


def dns_matmul(A: jax.Array, B: jax.Array, mesh: jax.sharding.Mesh,
               *, local_matmul: Callable | None = None,
               reduce_op: str | Callable = "sum") -> jax.Array:
    """Paper Algorithm 2::

        val GA = G mapD { case (i, j, k) => A(i)(k) }
        val GB = G mapD { case (i, j, k) => B(k)(j) }
        val C  = ((GA zipWithD GB)(_ * _) zSeq) reduceD (_ + _)

    ``mesh`` must have axes ('x', 'y', 'z') of equal size q.  The mapD lines
    are realized as shard_map in_specs: A arrives partitioned (x, z) — i.e.
    process (i, j, k) holds block A[i, k], replicated over y — and B arrives
    partitioned (z, y).  That *is* the static process↔data mapping; no data
    is moved to set it up (lazy/proxy semantics).
    """
    mm = local_matmul or (lambda a, b: a @ b)

    def body(a_blk, b_blk):
        g = Grid3D("x", "y", "z")
        c_partial = g.seq("z", a_blk).zipWithD(g.seq("z", b_blk), mm)
        # reduceD (+) along the z sequence; result replicated over z
        return c_partial.reduceD(reduce_op)

    fn = spmd(body, mesh, in_specs=(P("x", "z"), P("z", "y")), out_specs=P("x", "y"))
    return fn(A, B)


def generic_matmul(A: jax.Array, B: jax.Array, mesh: jax.sharding.Mesh,
                   axis: str = "z") -> jax.Array:
    """Paper Algorithm 1 (generic, for-loop): for every (i, j) block::

        A(i) zip Bt(j) mapD { case (a, b) => a * b } reduceD (_ + _)

    The 1-D communication group is mesh axis ``axis`` with q processes;
    process k holds A[i, k] and B[k, j] for the current (i, j).  The Python
    for-loop is the sequential ∀-emulation whose Θ(q²) nop overhead drives
    the Θ(p^{5/3}) isoefficiency of §4.2.1.
    """
    q = mesh.shape[axis]
    n = A.shape[0]
    blk = n // q
    assert n % q == 0

    def one_reduction(a_row, b_col):
        # a_row: (blk, n) sharded over axis into (blk, blk) pieces; same b_col.
        def body(a, b):
            prod = DSeq(a, axis).zipWithD(DSeq(b, axis), lambda x, y: x @ y)
            # exercise the generic tree-reduction path (user lambda _+_)
            return prod.reduceD(lambda u, v: u + v, root=None)

        return spmd(body, mesh, in_specs=(P(None, axis), P(axis, None)),
                    out_specs=P(None, None))(a_row, b_col)

    rows = []
    for i in range(q):
        cols = []
        for j in range(q):
            a_row = jax.lax.dynamic_slice_in_dim(A, i * blk, blk, 0)
            b_col = jax.lax.dynamic_slice_in_dim(B, j * blk, blk, 1)
            cols.append(one_reduction(a_row, b_col))
        rows.append(jnp.concatenate(cols, axis=1))
    return jnp.concatenate(rows, axis=0)


def dns_matmul_pallas(A: jax.Array, B: jax.Array,
                      mesh: jax.sharding.Mesh) -> jax.Array:
    """Algorithm 2 with the Pallas MXU kernel as the local multiply."""
    from repro.kernels.ops import matmul as pallas_matmul

    return dns_matmul(A, B, mesh,
                      local_matmul=pallas_matmul)
