"""Property checks for the arXiv:1406.6163 collectives (scanD,
reduceScatterD, ringShiftD, allGatherRingD) against their dense oracles on
4- and 8-process groups (run in a subprocess: needs 8 fake devices).

Uses hypothesis when installed; otherwise falls back to a fixed seed sweep
so the properties are still exercised in minimal environments.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
from repro.core.compat import make_mesh
from repro.core import spmd
from repro.core.dseq import (all_gather_ring_d, reduce_scatter_d, ring_shift_d,
                             scan_d)

MESHES = {p: make_mesh((p,), ("x",), devices=jax.devices()[:p])
          for p in (4, 8)}
_cache = {}


def _fn(key, p, build):
    """Jit once per (program, group size); hypothesis re-invokes with data."""
    if (key, p) not in _cache:
        _cache[(key, p)] = jax.jit(build(MESHES[p]))
    return _cache[(key, p)]


def check_scan(p: int, seed: int) -> None:
    x = jnp.array(np.random.RandomState(seed).randn(p, 5), jnp.float32)
    inc = _fn("inc", p, lambda m: spmd(
        lambda xl: scan_d(xl[0], "x", inclusive=True)[None], m,
        in_specs=P("x", None), out_specs=P("x", None)))
    np.testing.assert_allclose(np.asarray(inc(x)), np.cumsum(np.asarray(x), 0),
                               rtol=1e-5, atol=1e-5)
    exc = _fn("exc", p, lambda m: spmd(
        lambda xl: scan_d(xl[0], "x")[None], m,
        in_specs=P("x", None), out_specs=P("x", None)))
    want = np.concatenate([np.zeros((1, 5)), np.cumsum(np.asarray(x), 0)[:-1]])
    np.testing.assert_allclose(np.asarray(exc(x)), want, rtol=1e-5, atol=1e-5)
    mx = _fn("max", p, lambda m: spmd(
        lambda xl: scan_d(xl[0], "x", jnp.maximum, inclusive=True)[None], m,
        in_specs=P("x", None), out_specs=P("x", None)))
    np.testing.assert_allclose(np.asarray(mx(x)),
                               np.maximum.accumulate(np.asarray(x), 0), rtol=1e-5)


def check_reduce_scatter(p: int, seed: int) -> None:
    # rank r holds x[r] (a (p, 5) slab); the reduced sequence reshaped over
    # ranks must equal the psum oracle: chunk i of sum_r x[r] lands on rank i.
    x = jnp.array(np.random.RandomState(seed).randn(p, p, 5), jnp.float32)
    want = np.asarray(x).sum(0).reshape(p, 1, 5)
    for name, op in (("rs_sum", "sum"), ("rs_gen", lambda a, b: a + b)):
        f = _fn(name, p, lambda m, op=op: spmd(
            lambda xl: reduce_scatter_d(xl[0], op, "x")[None], m,
            in_specs=P("x", None, None), out_specs=P("x", None, None)))
        np.testing.assert_allclose(np.asarray(f(x)), want, rtol=1e-4, atol=1e-5)


def check_ring(p: int, seed: int) -> None:
    x = jnp.array(np.random.RandomState(seed).randn(p, 5), jnp.float32)
    sh = _fn("ring", p, lambda m: spmd(
        lambda xl: ring_shift_d(xl[0], "x")[None], m,
        in_specs=P("x", None), out_specs=P("x", None)))
    np.testing.assert_allclose(np.asarray(sh(x)),
                               np.roll(np.asarray(x), 1, axis=0), rtol=1e-6)
    ag = _fn("ag", p, lambda m: spmd(
        lambda xl: all_gather_ring_d(xl[0], "x"), m,
        in_specs=P("x", None), out_specs=P(None, None)))
    np.testing.assert_allclose(np.asarray(ag(x)), np.asarray(x), rtol=1e-6)


def run_all(p: int, seed: int) -> None:
    check_scan(p, seed)
    check_reduce_scatter(p, seed)
    check_ring(p, seed)


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=8, deadline=None)
    @given(p=st.sampled_from([4, 8]), seed=st.integers(0, 1000))
    def prop(p, seed):
        run_all(p, seed)

    prop()
except ImportError:
    for p in (4, 8):
        for seed in range(3):
            run_all(p, seed)

print("COLLECTIVES_OK")
