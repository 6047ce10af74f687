"""2D parallel matmul: SUMMA and Cannon on a Grid2D, vs the 3D DNS variant.

SUMMA broadcasts k-panels along grid rows/columns (van de Geijn & Watts);
Cannon skews both operands once, then only nearest-neighbour ring shifts.
Both hold Θ(n²/p) per process — no DNS-style operand replication — at the
price of a Θ(p^{3/2}) isoefficiency instead of DNS's Θ(p log p).

Run:  PYTHONPATH=src python examples/summa_matmul.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import sys, time
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
from repro.core.compat import make_mesh

import jax, jax.numpy as jnp, numpy as np
from repro.core import (cannon_matmul, cannon_matmul_25d, cannon_matmul_pallas,
                        dns_matmul, make_grid_mesh, summa_matmul,
                        summa_matmul_pallas, summa_matmul_pipelined)
from repro.core.costmodel import (cannon_25d_cost, cannon_matmul_cost,
                                  summa_matmul_cost, summa_pipelined_cost)
from repro.launch.roofline import matmul_scenarios_table

n = 512
A = jnp.array(np.random.RandomState(0).randn(n, n), jnp.float32)
B = jnp.array(np.random.RandomState(1).randn(n, n), jnp.float32)
want = np.asarray(A @ B)

# square 2x2 grid (4 of the 8 devices) and rectangular 2x4 grid (all 8)
mesh_sq = make_mesh((2, 2), ("x", "y"), devices=jax.devices()[:4])
mesh_rc = make_grid_mesh((2, 4), ("x", "y"))

for name, mesh in (("2x2", mesh_sq), ("2x4", mesh_rc)):
    C = jax.jit(lambda a, b: summa_matmul(a, b, mesh))(A, B)
    np.testing.assert_allclose(np.asarray(C), want, rtol=1e-3, atol=1e-3)
    C = jax.jit(lambda a, b: cannon_matmul(a, b, mesh))(A, B)
    np.testing.assert_allclose(np.asarray(C), want, rtol=1e-3, atol=1e-3)
    print(f"SUMMA + Cannon on {name} grid: correct")

# the same algorithms with the Pallas MXU kernel as the local multiply
np.testing.assert_allclose(np.asarray(summa_matmul_pallas(A, B, mesh_sq)),
                           want, rtol=1e-2, atol=1e-2)
np.testing.assert_allclose(np.asarray(cannon_matmul_pallas(A, B, mesh_sq)),
                           want, rtol=1e-2, atol=1e-2)
print("SUMMA + Cannon with Pallas local-multiply kernel: correct")

# the overlapped/replicated tier: pipelined SUMMA (ring transfers hidden
# behind compute) and 2.5D Cannon (2-fold replication on the 2x2x2 mesh)
mesh3 = make_grid_mesh((2, 2, 2), ("x", "y", "z"))
C = jax.jit(lambda a, b: summa_matmul_pipelined(a, b, mesh_rc))(A, B)
np.testing.assert_allclose(np.asarray(C), want, rtol=1e-3, atol=1e-3)
C = jax.jit(lambda a, b: cannon_matmul_25d(a, b, mesh3))(A, B)
np.testing.assert_allclose(np.asarray(C), want, rtol=1e-3, atol=1e-3)
print("pipelined SUMMA + 2.5D Cannon: correct")

# measured: the full five-variant scenario space on the same 8 chips
for name, fn in (("summa", lambda a, b: summa_matmul(a, b, mesh_rc)),
                 ("summa-pipe", lambda a, b: summa_matmul_pipelined(a, b, mesh_rc)),
                 ("cannon", lambda a, b: cannon_matmul(a, b, mesh_rc)),
                 ("cannon-2.5d", lambda a, b: cannon_matmul_25d(a, b, mesh3)),
                 ("dns", lambda a, b: dns_matmul(a, b, mesh3))):
    jitted = jax.jit(fn)
    jax.block_until_ready(jitted(A, B))
    t0 = time.perf_counter()
    jax.block_until_ready(jitted(A, B))
    print(f"{name:11s} {1e3 * (time.perf_counter() - t0):7.1f} ms")

# forecast at TPU scale: the full scenario table from the Table-1 cost model
print("\ncost-model forecast, n=40000 on 64 v5e chips:")
print(matmul_scenarios_table(40000, 64))
pred_s = summa_matmul_cost(40000, 8, bytes_per_elt=2)
pred_p = summa_pipelined_cost(40000, 2, 32, bytes_per_elt=2)
pred_c = cannon_matmul_cost(40000, 8, bytes_per_elt=2)
pred_25 = cannon_25d_cost(40000, 4, 4, bytes_per_elt=2)
print(f"\nSUMMA  E={pred_s['serial_s'] / (64 * pred_s['total_s']):.2f}   "
      f"SUMMA-pipe(2x32) E={pred_p['serial_s'] / (64 * pred_p['total_s']):.2f}   "
      f"Cannon E={pred_c['serial_s'] / (64 * pred_c['total_s']):.2f}   "
      f"Cannon-2.5D(4²x4) E={pred_25['serial_s'] / (64 * pred_25['total_s']):.2f}")
