"""MoE expert-parallel shard_map path vs single-device oracle (subprocess)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
from repro.core.compat import make_mesh
from repro.config import ModelConfig, MoEConfig
from repro.models.moe import moe_init, moe_ffn, MeshCtx

cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32, n_heads=4,
                  n_kv_heads=4, d_ff=64, vocab=64, block_pattern=("attn_moe",),
                  dtype="float32",
                  moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=16,
                                n_shared_experts=1, capacity_factor=8.0))
params = moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 32))

ref, ref_probs = moe_ffn(params, x, cfg, None)  # single-device oracle

mesh = make_mesh((2, 4), ("data", "model"))
ctx = MeshCtx(mesh=mesh, batch_axes=("data",), model_axis="model",
              fsdp_axes=("data",))

# EP layout: 8 experts over 4 shards (capacity_factor high => no drops)
got, probs = jax.jit(lambda p, x: moe_ffn(p, x, cfg, ctx))(params, x)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-2, atol=2e-3)
np.testing.assert_allclose(np.asarray(probs), np.asarray(ref_probs), rtol=1e-3, atol=1e-5)

# TP layout: 3 experts < 4 shards (dropless)
cfg2 = ModelConfig(name="t2", family="moe", n_layers=1, d_model=32, n_heads=4,
                   n_kv_heads=4, d_ff=64, vocab=64, block_pattern=("attn_moe",),
                   dtype="float32",
                   moe=MoEConfig(n_experts=3, top_k=2, d_ff_expert=16))
p2 = moe_init(jax.random.PRNGKey(2), cfg2)
ref2, _ = moe_ffn(p2, x, cfg2, None)
got2, _ = jax.jit(lambda p, x: moe_ffn(p, x, cfg2, ctx))(p2, x)
np.testing.assert_allclose(np.asarray(got2), np.asarray(ref2), rtol=2e-2, atol=2e-3)

# gradients flow through the sharded path
def loss(p):
    out, _ = moe_ffn(p, x, cfg, ctx)
    return jnp.sum(out ** 2)

g = jax.jit(jax.grad(loss))(params)
gn = jax.tree.reduce(lambda a, b: a + b,
                     jax.tree.map(lambda t: float(jnp.sum(jnp.abs(t))), g))
assert np.isfinite(gn) and gn > 0, gn

print("MOE_OK")

# a2a token-routing EP (§Perf H6) matches the oracle too
ctx3 = MeshCtx(mesh=mesh, batch_axes=("data",), model_axis="model",
               fsdp_axes=(), moe_a2a_ep=True)
got3, _ = jax.jit(lambda p, x: moe_ffn(p, x, cfg, ctx3))(params, x)
np.testing.assert_allclose(np.asarray(got3), np.asarray(ref), rtol=2e-2, atol=2e-3)
print("A2A_OK")
