"""SUMMA + Cannon + pipelined SUMMA + 2.5D Cannon vs the jnp.matmul oracle
on square (2x2), rectangular (2x4), and replicated (2x2x2) grids, including
the Pallas local-multiply path and the cost-model sanity ties (run in a
subprocess: needs 8 fake devices).

Uses hypothesis when installed; otherwise a fixed seed sweep.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
from repro.core.compat import make_mesh
from repro.core import (cannon_matmul, cannon_matmul_25d, costmodel,
                        summa_matmul, summa_matmul_pipelined)

MESHES = {
    (2, 2): make_mesh((2, 2), ("x", "y"), devices=jax.devices()[:4]),
    (2, 4): make_mesh((2, 4), ("x", "y")),
    (2, 2, 2): make_mesh((2, 2, 2), ("x", "y", "z")),
}
ALGS = {"summa": summa_matmul, "cannon": cannon_matmul,
        "summa_pipelined": summa_matmul_pipelined,
        "cannon_25d": cannon_matmul_25d}
_cache = {}


def _algs_for(grid):
    # 2.5D needs the q x q x c mesh; the 2D algorithms a 2-axis one
    return ("cannon_25d",) if len(grid) == 3 else (
        "summa", "cannon", "summa_pipelined")


def _fn(alg, grid):
    if (alg, grid) not in _cache:
        mesh = MESHES[grid]
        fn = ALGS[alg]
        _cache[(alg, grid)] = jax.jit(lambda a, b: fn(a, b, mesh))
    return _cache[(alg, grid)]


def check(grid, seed: int, n: int = 16) -> None:
    rng = np.random.RandomState(seed)
    A = jnp.array(rng.randn(n, n), jnp.float32)
    B = jnp.array(rng.randn(n, n), jnp.float32)
    want = np.asarray(A) @ np.asarray(B)
    for alg in _algs_for(grid):
        got = np.asarray(_fn(alg, grid)(A, B))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=8, deadline=None)
    @given(grid=st.sampled_from([(2, 2), (2, 4), (2, 2, 2)]),
           seed=st.integers(0, 1000))
    def prop(grid, seed):
        check(grid, seed)

    prop()
except ImportError:
    for grid in ((2, 2), (2, 4), (2, 2, 2)):
        for seed in range(3):
            check(grid, seed)

# rectangular operands: (m, k) @ (k, n) with m≠k≠n
rng = np.random.RandomState(7)
A = jnp.array(rng.randn(8, 32), jnp.float32)
B = jnp.array(rng.randn(32, 16), jnp.float32)
want = np.asarray(A) @ np.asarray(B)
for grid in ((2, 2), (2, 4), (2, 2, 2)):
    for alg in _algs_for(grid):
        got = np.asarray(_fn(alg, grid)(A, B))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

# ring-broadcast helpers ≡ tree broadcast (both row- and column-wise, every
# source): the pipelined primitive delivers exactly what apply_d does
from jax.sharding import PartitionSpec as P
from repro.core import spmd
from repro.core.grid import Grid2D

mesh24 = MESHES[(2, 4)]
x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
for src_col in range(4):
    def body(lx, s=src_col):
        g = Grid2D()
        st = g.bcast_row_ring_start(lx, s)
        for _ in range(3):          # q_y - 1 hops
            st = g.bcast_row_ring_next(st)
        return st.value - g.bcast_row(lx, s)
    diff = spmd(body, mesh24, in_specs=(P("x", "y"),),
                out_specs=P("x", "y"))(x)
    assert not np.asarray(diff).any(), (src_col, diff)
for src_row in range(2):
    def body(lx, s=src_row):
        g = Grid2D()
        st = g.bcast_col_ring_start(lx, s)
        st = g.bcast_col_ring_next(st)  # q_x - 1 = 1 hop
        assert st.done
        return st.value - g.bcast_col(lx, s)
    diff = spmd(body, mesh24, in_specs=(P("x", "y"),),
                out_specs=P("x", "y"))(x)
    assert not np.asarray(diff).any(), (src_row, diff)

# Pallas local multiply (interpret mode on CPU); the wrappers now use the
# accumulate-in-place MXU kernel so the panel loop updates C in one buffer
from repro.core import (cannon_matmul_25d_pallas, cannon_matmul_pallas,
                        summa_matmul_pallas, summa_matmul_pipelined_pallas)

A = jnp.array(rng.randn(16, 16), jnp.float32)
B = jnp.array(rng.randn(16, 16), jnp.float32)
want = np.asarray(A) @ np.asarray(B)
np.testing.assert_allclose(np.asarray(summa_matmul_pallas(A, B, MESHES[(2, 2)])),
                           want, rtol=1e-3, atol=1e-3)
np.testing.assert_allclose(np.asarray(cannon_matmul_pallas(A, B, MESHES[(2, 2)])),
                           want, rtol=1e-3, atol=1e-3)
np.testing.assert_allclose(
    np.asarray(summa_matmul_pipelined_pallas(A, B, MESHES[(2, 4)])),
    want, rtol=1e-3, atol=1e-3)
np.testing.assert_allclose(
    np.asarray(cannon_matmul_25d_pallas(A, B, MESHES[(2, 2, 2)])),
    want, rtol=1e-3, atol=1e-3)

# cost-model ties: predicted communication of Cannon never exceeds SUMMA's on
# the same square grid (no broadcast trees), both cover the same flops, and
# overlap pipelining only ever helps on the grids it targets
for n, q in ((1024, 2), (4096, 8)):
    cs = costmodel.summa_matmul_cost(n, q)
    cc = costmodel.cannon_matmul_cost(n, q)
    assert cc["compute_s"] == cs["compute_s"]
    assert cc["shift_s"] <= cs["broadcast_s"] * (1 + 1e-9), (cc, cs)
for n, qx, qy in ((512, 2, 4), (1024, 2, 2)):
    cs = costmodel.summa_matmul_cost(n, qx, qy)
    cp = costmodel.summa_pipelined_cost(n, qx, qy)
    assert cp["total_s"] <= cs["total_s"] * (1 + 1e-9), (cp, cs)

print("SUMMA_OK")
