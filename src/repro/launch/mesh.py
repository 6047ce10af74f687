"""Mesh construction.

Functions (not module constants) so importing never touches jax device
state.  ``make_production_mesh`` describes hardware this repo never runs on
(single pod 16×16 = 256 chips, axes (data, model); multi-pod 2×16×16 = 512
chips, axes (pod, data, model), ``pod`` mapping to the DCI link class in the
cost model): it serves the dry-run and the planner's analysis only.
``make_local_mesh`` spans whatever devices exist.
"""
from __future__ import annotations

import jax

from repro.core.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))
