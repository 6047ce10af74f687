import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, sys
sys.path.insert(0, __import__("os").path.join(__import__("os").path.dirname(__file__), "..", "..", "src"))
from repro.core.compat import make_mesh
from repro.config import ModelConfig
from repro.models import layers as L
from repro.models.moe import MeshCtx

cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=32, n_heads=4,
                  n_kv_heads=2, d_ff=64, vocab=64, dtype="float32", window=None)
p = L.attention_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
pos = jnp.arange(16)
ref, _ = L.attention(p, x, pos, cfg)
mesh = make_mesh((2, 4), ("data", "model"))
ctx = MeshCtx(mesh=mesh, batch_axes=("data",), manual_attention=True)
got, _ = jax.jit(lambda p, x: L.attention(p, x, pos, cfg, ctx=ctx))(p, x)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-3, atol=1e-4)
# SWA too
cfg2 = cfg.replace(window=4)
ref2, _ = L.attention(p, x, pos, cfg2)
got2, _ = jax.jit(lambda p, x: L.attention(p, x, pos, cfg2, ctx=ctx))(p, x)
np.testing.assert_allclose(np.asarray(got2), np.asarray(ref2), rtol=1e-3, atol=1e-4)
print("MANUAL_ATTN_OK")
