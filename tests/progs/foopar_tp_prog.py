import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, sys
sys.path.insert(0, __import__("os").path.join(__import__("os").path.dirname(__file__), "..", "..", "src"))
from repro.core.compat import make_mesh
from repro.config import ModelConfig
from repro.models import layers as L
from repro.models.moe import MeshCtx

cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=32, n_heads=4,
                  n_kv_heads=4, d_ff=64, vocab=64, dtype="float32")
p = L.mlp_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
ref = L.mlp(p, x, cfg)
mesh = make_mesh((2, 4), ("data", "model"))
ctx = MeshCtx(mesh=mesh, batch_axes=("data",), foopar_tp=True)
got = jax.jit(lambda p, x: L.mlp(p, x, cfg, ctx=ctx))(p, x)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-5)
# grads flow
g = jax.jit(jax.grad(lambda p: jnp.sum(L.mlp(p, x, cfg, ctx=ctx)**2)))(p)
assert all(np.isfinite(np.asarray(v)).all() for v in jax.tree.leaves(g))
print("FOOPAR_TP_OK")
