"""Distributed sequences: the FooPar Table-1 operation algebra in JAX.

A ``DSeq`` is the JAX realization of FooPar's ``DistributedSeq``: a sequence
whose *i*-th element lives on rank *i* of a communication group.  The
communication group is a mesh axis; the SPMD program is a ``shard_map`` body.
Inside that body each process holds its local element, and the Table-1 group
operations are implemented with ``jax.lax`` collectives:

  mapD / zipWithD   local compute (no communication)
  reduceD           psum/pmin/pmax fast path, or a generic binary-tree
                    reduction built from ppermute (log p rounds — the paper's
                    recursive-doubling cost  Θ(log p (t_s + t_w m + T_λ(m))))
  shiftD            ppermute cyclic shift            Θ(t_s + t_w m)
  allGatherD        all_gather                       Θ((t_s + t_w m)(p-1))
  allToAllD         all_to_all                       Θ(t_s log p + t_w m (p-1))
  applyD(i)         one-to-all broadcast (masked psum)  Θ(log p (t_s + t_w m))
  scanD             parallel prefix (Hillis-Steele)  Θ(log p (t_s + t_w m + T_λ(m)))
  reduceScatterD    ring reduce-scatter              Θ((p-1)(t_s + t_w m/p + T_λ(m/p)))
  ringShiftD        ±1 nearest-neighbour shift       Θ(t_s + t_w m)
  allGatherRingD    pipelined ring all-gather        Θ((t_s + t_w m)(p-1))

The scan / reduce-scatter / ring family is the arXiv:1406.6163 extension of
the Table-1 algebra (group communication patterns beyond the 2013 paper).

Deadlock-freedom and race-freedom hold by construction: the ops are pure
functions on a dataflow graph; there is no user-visible message passing.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .compat import shard_map as _shard_map

Pytree = Any

# ---------------------------------------------------------------------------
# Low-level SPMD group operations (usable directly inside any shard_map body).
# ---------------------------------------------------------------------------


def axis_index(axis: str) -> jax.Array:
    return lax.axis_index(axis)


def _where_bcast(cond: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """where with a scalar predicate, broadcast over the operand rank."""
    return jnp.where(jnp.reshape(cond, (1,) * a.ndim), a, b)


def reduce_d(x: Pytree, op: Callable | str, axis: str, *, root: int | None = None) -> Pytree:
    """FooPar ``reduceD``: reduce the distributed sequence with associative
    ``op``.

    ``op`` may be one of the strings ``'sum' | 'min' | 'max'`` (lowers to the
    native XLA all-reduce, recursive-doubling on a torus) or an arbitrary
    associative callable, in which case a binary-tree reduction is built from
    ``ppermute`` — ``ceil(log2 p)`` rounds, each moving one element of size m:
    the paper's  Θ(log p (t_s + t_w m + T_λ(m))).

    FooPar reduces *to the root*; XLA exposes all-reduce.  Semantics are kept
    (with ``root`` given, non-root processes receive a zero element whose
    value must not be used); cost is identical in Θ.
    """
    if isinstance(op, str):
        fast = {"sum": lax.psum, "min": lax.pmin, "max": lax.pmax}[op]
        out = jax.tree.map(lambda l: fast(l, axis), x)
        if root is None:
            return out
        idx = lax.axis_index(axis)
        return jax.tree.map(lambda l: jnp.where(idx == root, l, jnp.zeros_like(l)), out)

    p = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    rounds = max(1, math.ceil(math.log2(p))) if p > 1 else 0
    for r in range(rounds):
        stride = 1 << r
        block = stride << 1
        # senders: i with i % block == stride and i - stride >= 0
        perm = [(i + stride, i) for i in range(0, p, block) if i + stride < p]
        recv = jax.tree.map(lambda l: lax.ppermute(l, axis, perm), x)
        combined = op(x, recv)
        is_dst = (idx % block == 0) & (idx + stride < p)
        x = jax.tree.map(
            lambda c, old: jnp.where(
                jnp.reshape(is_dst, (1,) * c.ndim), c, old
            ),
            combined,
            x,
        )
    # result now at rank 0; replicate if root is None (broadcast), else mask.
    if root is None:
        return apply_d(x, 0, axis)
    if root != 0:
        x = shift_d(x, root, axis)  # move result from 0 to root (cyclic ok)
    return jax.tree.map(
        lambda l: jnp.where(lax.axis_index(axis) == root, l, jnp.zeros_like(l)), x
    )


def shift_d(x: Pytree, delta: int, axis: str) -> Pytree:
    """FooPar ``shiftD``: cyclic shift by ``delta`` — Θ(t_s + t_w m)."""
    p = lax.axis_size(axis)
    d = delta % p
    if d == 0:
        return x
    perm = [(i, (i + d) % p) for i in range(p)]
    return jax.tree.map(lambda l: lax.ppermute(l, axis, perm), x)


def all_gather_d(x: Pytree, axis: str, *, tiled: bool = False) -> Pytree:
    """FooPar ``allGatherD`` — Θ((t_s + t_w m)(p-1)) on a ring."""
    return jax.tree.map(lambda l: lax.all_gather(l, axis, axis=0, tiled=tiled), x)


def all_to_all_d(x: Pytree, axis: str) -> Pytree:
    """FooPar ``allToAllD``: local leading dim indexes destination rank."""
    return jax.tree.map(
        lambda l: lax.all_to_all(l, axis, split_axis=0, concat_axis=0, tiled=True), x
    )


def apply_d(x: Pytree, i: int | jax.Array, axis: str) -> Pytree:
    """FooPar ``apply(i)``: every process obtains element *i* — a one-to-all
    broadcast, Θ(log p (t_s + t_w m)).  Implemented as the classic masked-psum
    idiom, which XLA lowers to a log-p broadcast tree."""
    idx = lax.axis_index(axis)
    return jax.tree.map(
        lambda l: lax.psum(
            jnp.where(jnp.reshape(idx == i, (1,) * l.ndim), l, jnp.zeros_like(l)),
            axis,
        ),
        x,
    )


def scan_d(x: Pytree, axis: str, op: Callable | None = None, *,
           inclusive: bool = False) -> Pytree:
    """Parallel prefix over the group (arXiv:1406.6163 ``scanD``).

    Hillis-Steele recursive doubling: ``ceil(log2 p)`` rounds of ppermute,
    each combining with the neighbour ``stride`` ranks below —
    Θ(log p (t_s + t_w m + T_λ(m))).  ``op`` is any associative callable
    (default elementwise ``+``).  ``inclusive=False`` (default) returns the
    exclusive prefix: rank 0 gets the identity (zeros — only meaningful for
    ``+``-like ops), rank i gets ``op``-fold of elements 0..i-1.
    """
    op = op or (lambda a, b: a + b)
    idx = lax.axis_index(axis)
    p = lax.axis_size(axis)
    acc = x
    for r in range(max(0, math.ceil(math.log2(p)))):
        stride = 1 << r
        perm = [(i, i + stride) for i in range(p - stride)]
        recv = jax.tree.map(lambda l: lax.ppermute(l, axis, perm), acc)
        take = idx >= stride
        combined = jax.tree.map(lambda a, rv: op(rv, a), acc, recv)
        acc = jax.tree.map(
            lambda c, a: _where_bcast(take, c, a), combined, acc,
        )
    if inclusive:
        return acc
    # convert inclusive -> exclusive (identity = zeros at rank 0)
    shifted = jax.tree.map(
        lambda l: lax.ppermute(l, axis, [(i, i + 1) for i in range(p - 1)]), acc)
    return jax.tree.map(
        lambda s: _where_bcast(idx == 0, jnp.zeros_like(s), s), shifted,
    )


def reduce_scatter_d(x: Pytree, op: Callable | str, axis: str) -> Pytree:
    """``reduceScatterD`` (arXiv:1406.6163): reduce the sequence with ``op``
    and leave rank i holding the i-th chunk of the result (leading dim is
    split p ways).

    ``op == 'sum'`` lowers to the native ``psum_scatter``.  A callable ``op``
    runs the classic ring algorithm: p-1 nearest-neighbour steps, each moving
    one m/p chunk — Θ((p-1)(t_s + t_w m/p + T_λ(m/p))), the bandwidth-optimal
    half of an all-reduce.
    """
    if isinstance(op, str):
        assert op == "sum", op
        return jax.tree.map(
            lambda l: lax.psum_scatter(l, axis, scatter_dimension=0, tiled=True),
            x,
        )

    p = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    ring = [(i, (i + 1) % p) for i in range(p)]
    for l in jax.tree.leaves(x):
        if l.shape[0] % p:
            raise ValueError(
                f"reduce_scatter_d: leading dim {l.shape[0]} must be "
                f"divisible by group size {p}")

    def chunk(l: jax.Array, c: jax.Array) -> jax.Array:
        blk = l.shape[0] // p
        return lax.dynamic_slice_in_dim(l, c * blk, blk, axis=0)

    # chunk c travels the ring from rank c+1 to rank c, accumulating each
    # host's contribution; rank r therefore sends the partial of chunk
    # (r - s - 1) at step s and finishes holding chunk r.
    if p == 1:
        return x
    buf = jax.tree.map(lambda l: chunk(l, (idx - 1) % p), x)
    for s in range(p - 1):
        sent = jax.tree.map(lambda l: lax.ppermute(l, axis, ring), buf)
        c_recv = (idx - s - 2) % p
        buf = jax.tree.map(lambda rv, l: op(rv, chunk(l, c_recv)), sent, x)
    return buf


def ring_shift_d(x: Pytree, axis: str, *, reverse: bool = False) -> Pytree:
    """Nearest-neighbour ring step (±1 cyclic shift) — Θ(t_s + t_w m).

    The building block of the pipelined ("systolic") variants below and of
    Cannon's algorithm: every rank passes its element to rank+1 (or rank-1
    with ``reverse``), so p-1 applications rotate the full sequence past
    every rank with only nearest-neighbour traffic.
    """
    return shift_d(x, -1 if reverse else 1, axis)


def all_gather_ring_d(x: Pytree, axis: str) -> Pytree:
    """Pipelined ring all-gather: p-1 ``ring_shift_d`` steps, concatenating
    the block received at each step — Θ((t_s + t_w m)(p-1)), identical in Θ
    to the native all-gather but expressed in the algebra (and usable with
    compute overlapped between steps, as in pipelined SUMMA)."""
    p = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    parts = [jax.tree.map(lambda l: l, x)]
    buf = x
    for _ in range(p - 1):
        buf = ring_shift_d(buf, axis)
        parts.append(buf)
    # parts[s] is the element of rank (idx - s) % p; roll into rank order so
    # position j of the output holds element j, matching all_gather_d.
    def assemble(*ls):
        stacked = jnp.stack(ls, axis=0)  # (p, ...) in arrival order
        order = (idx - jnp.arange(p)) % p
        return jnp.zeros_like(stacked).at[order].set(stacked)

    return jax.tree.map(lambda *ls: assemble(*ls), *parts)


# ---------------------------------------------------------------------------
# DSeq: the object-oriented face of the algebra (paper §3.3), for use inside
# shard_map bodies.  Chains read like the paper:  seq.mapD(f).reduceD('+').
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DSeq:
    """A distributed sequence bound to communication group ``axis``.

    ``local`` is this process's element (any pytree of arrays).  Element *i*
    of the abstract sequence lives on rank *i* of the mesh axis.
    """

    local: Pytree
    axis: str

    # -- non-communicating ------------------------------------------------
    def mapD(self, f: Callable) -> "DSeq":
        return DSeq(f(self.local), self.axis)

    def mapIdxD(self, f: Callable) -> "DSeq":
        """map with the element index (= rank) as first argument."""
        return DSeq(f(lax.axis_index(self.axis), self.local), self.axis)

    def zipWithD(self, other: "DSeq", f: Callable) -> "DSeq":
        assert other.axis == self.axis, "zipWithD requires the same group"
        return DSeq(f(self.local, other.local), self.axis)

    # -- communicating (Table 1) ------------------------------------------
    def reduceD(self, op: Callable | str, root: int | None = None) -> Pytree:
        return reduce_d(self.local, op, self.axis, root=root)

    def shiftD(self, delta: int) -> "DSeq":
        return DSeq(shift_d(self.local, delta, self.axis), self.axis)

    def allGatherD(self, tiled: bool = False) -> Pytree:
        return all_gather_d(self.local, self.axis, tiled=tiled)

    def allToAllD(self) -> "DSeq":
        return DSeq(all_to_all_d(self.local, self.axis), self.axis)

    def apply(self, i: int | jax.Array) -> Pytree:
        return apply_d(self.local, i, self.axis)

    def scanD(self, op: Callable | None = None, *, inclusive: bool = False) -> "DSeq":
        return DSeq(scan_d(self.local, self.axis, op, inclusive=inclusive), self.axis)

    def reduceScatterD(self, op: Callable | str = "sum") -> "DSeq":
        return DSeq(reduce_scatter_d(self.local, op, self.axis), self.axis)

    def ringShiftD(self, *, reverse: bool = False) -> "DSeq":
        return DSeq(ring_shift_d(self.local, self.axis, reverse=reverse), self.axis)

    def allGatherRingD(self) -> Pytree:
        return all_gather_ring_d(self.local, self.axis)

    # -- introspection -----------------------------------------------------
    @property
    def size(self) -> int:
        return lax.axis_size(self.axis)

    @property
    def rank(self) -> jax.Array:
        return lax.axis_index(self.axis)


def spmd(
    f: Callable,
    mesh: jax.sharding.Mesh,
    in_specs,
    out_specs,
    *,
    check_vma: bool = False,
):
    """Run ``f`` as a FooPar SPMD program over ``mesh``.

    Thin wrapper over ``jax.shard_map`` — every process executes ``f`` on its
    shard; group operations on DSeq objects are the only communication.
    """
    return _shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check=check_vma
    )
