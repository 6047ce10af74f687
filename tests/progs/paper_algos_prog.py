"""Multi-device paper-algorithm checks (subprocess: 8 fake devices)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
from repro.core.compat import make_mesh
from repro.core import (dns_matmul, dns_matmul_pallas, generic_matmul,
                        floyd_warshall, blocked_floyd_warshall,
                        floyd_warshall_reference, make_grid_mesh)

rng = np.random.RandomState(0)

# DNS (Grid3D) matmul, 2x2x2 grid
mesh3 = make_grid_mesh((2, 2, 2), ("x", "y", "z"))
n = 32
A = jnp.array(rng.randn(n, n), jnp.float32)
B = jnp.array(rng.randn(n, n), jnp.float32)
np.testing.assert_allclose(np.asarray(dns_matmul(A, B, mesh3)),
                           np.asarray(A @ B), rtol=1e-3, atol=1e-4)

# DNS with the Pallas local-multiply kernel (interpret mode)
np.testing.assert_allclose(np.asarray(dns_matmul_pallas(A, B, mesh3)),
                           np.asarray(A @ B), rtol=1e-3, atol=1e-3)

# generic (Algorithm 1) with the for-loop emulation, 8-process group
np.testing.assert_allclose(
    np.asarray(generic_matmul(A, B, make_grid_mesh((8,), ("z",)), axis="z")),
    np.asarray(A @ B), rtol=1e-3, atol=1e-4)

# Floyd-Warshall, 2x2 grid (n=24)
mesh2 = make_grid_mesh((2, 2), ("x", "y"))
n = 24
W = rng.rand(n, n).astype(np.float32) * 10
W[np.diag_indices(n)] = 0
D = jnp.array(W)
ref = floyd_warshall_reference(D)
np.testing.assert_allclose(np.asarray(floyd_warshall(D, mesh2)),
                           np.asarray(ref), rtol=1e-5)
np.testing.assert_allclose(np.asarray(blocked_floyd_warshall(D, mesh2)),
                           np.asarray(ref), rtol=1e-5)

# FooPar TP matmuls (algebra inside pjit)
from repro.core.tensor_ops import foopar_matmul_row, foopar_matmul_col, dns_matmul_2d
mesh = make_mesh((2, 4), ("data", "model"))
x = jnp.array(rng.randn(16, 8), jnp.float32)
w = jnp.array(rng.randn(8, 12), jnp.float32)
ref = np.asarray(x) @ np.asarray(w)
for fn in (foopar_matmul_row, foopar_matmul_col, dns_matmul_2d):
    got = jax.jit(lambda a, b, fn=fn: fn(a, b, mesh=mesh))(x, w)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-4)

print("ALGOS_OK")
