"""Oracle: the ZeRO reduce-scatter train step matches the all-reduce step's
training trajectory on a 1×8 CPU mesh (f32 end to end).

The two steps share every numeric op — loss, grads, clip (taken on the
reduced grads *before* the scatter) and the per-element AdamW math — so the
loss trajectory must agree bit-for-bit in f32; the updated params may differ
by reduction-layout ulps (all-gathered shard vs replicated update), bounded
tightly.  Also asserts the layout actually scattered: optimizer moments live
as 1/8 shards, and the plan lattice only offers the zero strategy where
there is a group to scatter over.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
from repro.core.compat import make_mesh
from repro import configs
from repro.config import ParallelConfig, ShapeConfig, TrainConfig
from repro.data import make_batch_iterator
from repro.launch.train import reduced
from repro.parallel import steps as S
from repro.parallel.sharding import make_ctx, param_specs, scatter_specs

STEPS = 6


def run(grad: str, mesh, cfg, tcfg):
    pcfg = ParallelConfig(remat="none", fsdp_params=False,
                          grad_dtype="float32", grad_reduce=grad)
    ctx = make_ctx(mesh, pcfg)
    state = S.init_train_state(jax.random.PRNGKey(0), cfg, pcfg)
    sh = S.train_state_shardings(cfg, pcfg, ctx, state)
    state = jax.device_put(state, sh)
    bsh = {"tokens": NamedSharding(mesh, P(("data",), None))}
    step = jax.jit(S.make_train_step(cfg, pcfg, tcfg, ctx),
                   in_shardings=(sh, bsh), out_shardings=(sh, None),
                   donate_argnums=(0,))
    losses = []
    it = make_batch_iterator(cfg, ShapeConfig("t", "train", 64, 8))
    for _, batch in zip(range(STEPS), it):
        state, m = step(state, jax.device_put(batch, bsh))
        losses.append(float(m["loss"]))
    return losses, state


def main():
    assert len(jax.devices()) == 8
    cfg = reduced(configs.get("llama3.2-3b")).replace(
        vocab=64, dtype="float32", param_dtype="float32")
    mesh = make_mesh((8, 1), ("data", "model"))
    tcfg = TrainConfig(lr=3e-3, warmup_steps=2, total_steps=20, z_loss=0.0)

    losses_ar, state_ar = run("all_reduce", mesh, cfg, tcfg)
    losses_z, state_z = run("reduce_scatter_zero", mesh, cfg, tcfg)

    # trajectory: bit-for-bit in f32
    assert losses_ar == losses_z, (losses_ar, losses_z)
    assert losses_ar[-1] < losses_ar[0], losses_ar

    # params: all-gathered shard update ≡ replicated update (layout ulps only)
    for a, b in zip(jax.tree.leaves(jax.device_get(state_ar["params"])),
                    jax.tree.leaves(jax.device_get(state_z["params"]))):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-6)

    # the zero layout really is scattered: at least one moment leaf stores a
    # strict 1/8 shard per device...
    scattered = sum(
        1 for leaf in jax.tree.leaves(state_z["opt"]["m"])
        if np.prod(leaf.addressable_shards[0].data.shape) * 8
        == np.prod(leaf.shape))
    assert scattered > 0, "no optimizer moment was reduce-scattered"
    # ... while the all-reduce layout keeps full replicas (model axis is 1)
    for leaf in jax.tree.leaves(state_ar["opt"]["m"]):
        assert leaf.addressable_shards[0].data.shape == leaf.shape

    # scatter_specs sanity on the same tree: fsdp-off specs gain the data
    # axis on a divisible dim; indivisible leaves stay put
    ctx = make_ctx(mesh, ParallelConfig(remat="none", fsdp_params=False))
    params = jax.device_get(state_ar["params"])
    sspec = scatter_specs(params, cfg, ctx)
    pspec = param_specs(params, cfg, ctx)
    changed = sum(1 for s, p_ in zip(jax.tree.leaves(sspec, is_leaf=lambda x: isinstance(x, P)),
                                     jax.tree.leaves(pspec, is_leaf=lambda x: isinstance(x, P)))
                  if s != p_)
    assert changed > 0, "scatter_specs added no scatter axes"

    print("ZERO_OK")


if __name__ == "__main__":
    main()
