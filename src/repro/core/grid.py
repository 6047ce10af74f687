"""Cartesian grid abstractions (paper §4.3): GridN / Grid2D / Grid3D.

A grid binds N mesh axes into a Cartesian process grid.  Each process has a
coordinate tuple; ``seq(axis)`` returns the DSeq that is *variable* in that
axis and constant in all the others — the paper's ``xSeq / ySeq / zSeq``.
This is what lets multi-axis algorithms (DNS matmul, Floyd-Warshall) be
written as chained functional ops per axis, with the Table-1 costs applying
per-axis (group size = the axis extent, not p).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .compat import make_mesh
from .dseq import DSeq, apply_d, reduce_d, ring_shift_d, shift_d

Pytree = Any


@dataclass(frozen=True)
class RingBcast:
    """An in-flight pipelined ring broadcast along one mesh axis.

    A tree broadcast (``apply_d``) delivers in Θ(log p) but every step of a
    panel loop must wait for the whole tree.  A *ring* broadcast instead
    forwards the element one nearest-neighbour hop per ``step()`` —
    Θ(t_s + t_w m) each — so a caller can interleave hops of panel k+1's
    broadcast with the local multiply of panel k (double buffering): the
    transfer is hidden behind compute instead of serialized with it.

    ``buf`` holds the broadcast value on every rank whose forward ring
    distance from ``src`` is ≤ ``hops``.  Other ranks still hold their own
    local element — no zero-masking is needed, because each rank's hop-h
    select overwrites its buffer from its predecessor exactly when the
    value arrives (distance h), before anything reads it.  After ``p - 1``
    steps the value is everywhere and ``value`` may be read.
    """

    buf: Pytree
    src: Any  # int | jax.Array
    hops: int
    axis: str

    @classmethod
    def start(cls, local: Pytree, src, axis: str) -> "RingBcast":
        return cls(buf=local, src=src, hops=0, axis=axis)

    def step(self) -> "RingBcast":
        """Advance one nearest-neighbour hop (``ring_shift_d``)."""
        p = lax.axis_size(self.axis)
        if self.hops >= p - 1:
            return self
        idx = lax.axis_index(self.axis)
        # the value arrives at ring distance d exactly at hop d; lax.rem on
        # the made-nonnegative distance avoids jnp.%'s sign-fixup op chain
        arriving = lax.rem(idx - self.src + p, p) == self.hops + 1
        recv = ring_shift_d(self.buf, self.axis)
        buf = jax.tree.map(
            lambda b, r: jnp.where(jnp.reshape(arriving, (1,) * b.ndim), r, b),
            self.buf, recv,
        )
        return RingBcast(buf=buf, src=self.src, hops=self.hops + 1, axis=self.axis)

    @property
    def done(self) -> bool:
        return self.hops >= lax.axis_size(self.axis) - 1

    @property
    def value(self) -> Pytree:
        assert self.done, (self.hops, self.axis)
        return self.buf


@dataclass(frozen=True)
class GridN:
    """An N-dimensional Cartesian process grid over mesh axes ``axes``.

    Used inside a ``shard_map`` body whose mesh contains those axes.  The
    process's coordinate is ``self.coords`` (a tuple of traced ints).
    """

    axes: Tuple[str, ...]

    @property
    def ndim(self) -> int:
        return len(self.axes)

    @property
    def coords(self) -> Tuple[jax.Array, ...]:
        return tuple(lax.axis_index(a) for a in self.axes)

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(lax.axis_size(a) for a in self.axes)

    def mapD(self, f: Callable[..., Pytree]) -> Pytree:
        """Each process computes ``f(*coords)`` — the paper's
        ``G mapD { case (i, j, k) => ... }`` (non-communicating; lazy/proxy
        data is materialized per-process here)."""
        return f(*self.coords)

    def seq(self, axis: str, local: Pytree) -> DSeq:
        """The distributed sequence variable in ``axis``, constant in the
        remaining coordinates (paper's xSeq/ySeq/zSeq)."""
        assert axis in self.axes
        return DSeq(local, axis)


class Grid2D(GridN):
    """A q_x × q_y process grid.  Convention: the ``x`` axis indexes the
    process *row* i, ``y`` the process *column* j — so a "row" of the grid is
    the communication group that varies in y (all columns of one row), and
    row-wise collectives run over the y axis.

    The row/column broadcast + reduce helpers below are the primitives of
    the 2D matmul family (SUMMA's k-panel broadcasts, Cannon's ring shifts)
    and of the 2D Floyd-Warshall — paper §4.3/§5."""

    def __init__(self, x_axis: str = "x", y_axis: str = "y"):
        super().__init__(axes=(x_axis, y_axis))

    @property
    def row_axis(self) -> str:  # the axis a row-wise collective runs over
        return self.axes[1]

    @property
    def col_axis(self) -> str:
        return self.axes[0]

    def xSeq(self, local: Pytree) -> DSeq:  # variable in x, fixed y
        return self.seq(self.axes[0], local)

    def ySeq(self, local: Pytree) -> DSeq:
        return self.seq(self.axes[1], local)

    # -- 2D collective helpers (SUMMA / Cannon / FW building blocks) -------
    def bcast_row(self, local: Pytree, src_col: int | jax.Array) -> Pytree:
        """One-to-all broadcast within each process row: every process of row
        i receives the element held at (i, src_col) — Θ(log q_y (t_s + t_w m))."""
        return apply_d(local, src_col, self.row_axis)

    def bcast_col(self, local: Pytree, src_row: int | jax.Array) -> Pytree:
        """Broadcast within each process column from row ``src_row``."""
        return apply_d(local, src_row, self.col_axis)

    def reduce_row(self, local: Pytree, op: Callable | str = "sum",
                   root: int | None = None) -> Pytree:
        """reduceD over each process row (the y group)."""
        return reduce_d(local, op, self.row_axis, root=root)

    def reduce_col(self, local: Pytree, op: Callable | str = "sum",
                   root: int | None = None) -> Pytree:
        return reduce_d(local, op, self.col_axis, root=root)

    def shift_row(self, local: Pytree, delta: int) -> Pytree:
        """Cyclic shift within each process row (Cannon's A-movement)."""
        return shift_d(local, delta, self.row_axis)

    def shift_col(self, local: Pytree, delta: int) -> Pytree:
        return shift_d(local, delta, self.col_axis)

    # -- pipelined (double-buffered) ring broadcasts -----------------------
    def bcast_row_ring_start(self, local: Pytree, src_col) -> RingBcast:
        """Begin a pipelined ring broadcast within each process row from
        column ``src_col``.  Unlike ``bcast_row`` (a log-tree ``apply_d``),
        the transfer advances one nearest-neighbour hop per
        ``bcast_row_ring_next`` call, so the caller can issue panel k+1's
        hops before panel k's local multiply (pipelined SUMMA)."""
        return RingBcast.start(local, src_col, self.row_axis)

    def bcast_row_ring_next(self, st: RingBcast) -> RingBcast:
        assert st.axis == self.row_axis
        return st.step()

    def bcast_col_ring_start(self, local: Pytree, src_row) -> RingBcast:
        """Column-wise twin of ``bcast_row_ring_start`` (over the x axis)."""
        return RingBcast.start(local, src_row, self.col_axis)

    def bcast_col_ring_next(self, st: RingBcast) -> RingBcast:
        assert st.axis == self.col_axis
        return st.step()

    def skew(self, local: Pytree, *, by_row: bool, scale: int = 1) -> Pytree:
        """Cannon's alignment step as one grid-wide ppermute.

        ``by_row=True`` sends (i, j) → (i, j - i·scale mod q_y) — row i's
        blocks rotate left by i·scale (A's skew); ``by_row=False`` sends
        (i, j) → (i - j·scale mod q_x, j) (B's skew).  A single ppermute over
        the linearized grid, Θ(t_s + t_w m): per-row distances differ, which
        a single-axis shift cannot express.
        """
        qx, qy = self.shape
        perm = []
        for i in range(qx):
            for j in range(qy):
                if by_row:
                    dst = (i, (j - i * scale) % qy)
                else:
                    dst = ((i - j * scale) % qx, j)
                perm.append((i * qy + j, dst[0] * qy + dst[1]))
        return jax.tree.map(lambda l: lax.ppermute(l, self.axes, perm), local)


class Grid3D(GridN):
    def __init__(self, x_axis: str = "x", y_axis: str = "y", z_axis: str = "z"):
        super().__init__(axes=(x_axis, y_axis, z_axis))

    def xSeq(self, local: Pytree) -> DSeq:
        return self.seq(self.axes[0], local)

    def ySeq(self, local: Pytree) -> DSeq:
        return self.seq(self.axes[1], local)

    def zSeq(self, local: Pytree) -> DSeq:
        return self.seq(self.axes[2], local)


def make_grid_mesh(shape: Sequence[int], axes: Sequence[str] | None = None) -> jax.sharding.Mesh:
    """Build a device mesh for an N-d grid on the available devices."""
    axes = tuple(axes) if axes is not None else tuple("xyzw"[: len(shape)])
    return make_mesh(shape, axes)
