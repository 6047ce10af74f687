"""The two mesh-level spellings the whole repo shares.

``make_mesh`` pins every axis to ``AxisType.Auto``: the code is written for
GSPMD-style propagation (``with_sharding_constraint`` hints, partial-manual
``shard_map``), and ``jax.make_mesh`` now defaults to ``Explicit`` axes,
which refuse both.  ``shard_map`` keeps the repo's ``check`` /
``axis_names`` keywords on one spelling.
"""
from __future__ import annotations

from typing import Any, Callable, Iterable, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with ``Auto`` axes (every mesh in the repo)."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def shard_map(
    f: Callable,
    *,
    mesh: jax.sharding.Mesh,
    in_specs: Any,
    out_specs: Any,
    axis_names: Iterable[str] | None = None,
    check: bool = False,
) -> Callable:
    """``jax.shard_map``; ``axis_names`` lists the *manual* axes (all of
    them when None), ``check`` maps to ``check_vma``."""
    kw = {} if axis_names is None else {"axis_names": frozenset(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check, **kw)
