"""Training launcher: end-to-end driver (example application (b)).

On the CPU container this trains a reduced config on a small local mesh; on
a real cluster the same entry point runs the production mesh (the step
function, sharding rules, and checkpoint path are identical — only the mesh
size changes).

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --steps 50 \
      --reduce --batch 8 --seq 256

Fault tolerance is on by default: step-fenced checkpoints + crash-only
restart loop (runtime/recovery.py); ``--inject-fault-at N`` proves recovery.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.config import (ModelConfig, ParallelConfig, ShapeConfig, TrainConfig)
from repro.data import make_batch_iterator
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.parallel import planner
from repro.parallel import steps as S
from repro.parallel.sharding import make_ctx, param_specs, to_shardings
from repro.runtime import TrainingRunner
from repro import checkpoint as ckpt


def reduced(cfg: ModelConfig) -> ModelConfig:
    """Shrink an arch config to a CPU-trainable size, same family/topology."""
    import dataclasses
    kw = dict(n_layers=len(cfg.block_pattern), d_model=128, n_heads=4,
              n_kv_heads=min(4, cfg.n_kv_heads), d_ff=256 if cfg.d_ff else 0,
              vocab=512, head_dim=32)
    if cfg.moe:
        kw["moe"] = dataclasses.replace(cfg.moe, n_experts=4, top_k=2,
                                        d_ff_expert=128)
    if cfg.ssm:
        kw["ssm"] = dataclasses.replace(cfg.ssm, d_state=16, head_dim=16, chunk=32)
    if cfg.xlstm:
        kw["xlstm"] = dataclasses.replace(cfg.xlstm, chunk=32)
    if cfg.window:
        kw["window"] = 64
    return cfg.replace(**kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    # BooleanOptionalAction so --no-reduce can actually turn it off (the old
    # store_true + default=True pair made the flag impossible to disable)
    ap.add_argument("--reduce", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--plan", default="default",
                    choices=["default", "auto", "zero", "allreduce"],
                    help="parallel layout: 'auto' runs the cost-model "
                         "plan_search on the local mesh; zero/allreduce pin "
                         "the gradient strategy")
    # 3e-3 (with the seeded init/data below) descends within even 8-step
    # smoke runs; 1e-3 needs tens of steps to clear the warmup ramp
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--inject-fault-at", type=int, default=None)
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args()

    use_compile_cache()
    cfg = configs.get(args.arch)
    if args.reduce:
        cfg = reduced(cfg)
    shape = ShapeConfig("train_cli", "train", args.seq, args.batch)
    n_dev = len(jax.devices())
    mesh = make_local_mesh(model=args.model_parallel)
    if args.plan == "auto":
        # cost-driven layout on the local mesh (a ParallelPlan, ranked by
        # the Table-1 step model); top feasible point wins
        ranked = planner.plan_search(
            cfg, tuple(mesh.shape[a] for a in mesh.axis_names),
            args.batch, args.seq, "train",
            axis_names=tuple(mesh.axis_names))
        plan = planner.best_plan(ranked)   # same f32-moments numerics guard
        top = next(r for r in ranked if r.plan is plan)
        print(f"plan_search picked: {plan.label()} "
              f"(predicted {top.total_s * 1e3:.2f} ms/step)")
        pcfg = plan.to_pcfg()
    else:
        grad = {"zero": "reduce_scatter_zero"}.get(args.plan, "all_reduce")
        pcfg = ParallelConfig(remat="none", fsdp_params=False,
                              grad_reduce=grad)
    # warmup must fit inside short smoke runs (the fault-injection test does 8
    # steps) or the effective lr never leaves the ramp and the loss plateaus
    warmup = max(1, min(10, args.steps // 4))
    tcfg = TrainConfig(lr=args.lr, warmup_steps=warmup, total_steps=args.steps,
                       checkpoint_every=args.ckpt_every,
                       checkpoint_dir=args.ckpt_dir, z_loss=0.0)

    ctx = make_ctx(mesh, pcfg) if n_dev > 1 else None

    train_step = S.make_train_step(cfg, pcfg, tcfg, ctx)
    jitted = jax.jit(train_step, donate_argnums=(0,))

    def build(start_step: int):
        if ckpt.latest_step(args.ckpt_dir):
            like = S.abstract_train_state(cfg, pcfg)
            state = ckpt.restore_checkpoint(args.ckpt_dir, start_step, like)
        else:
            state = S.init_train_state(jax.random.PRNGKey(tcfg.seed), cfg, pcfg)
        batches = make_batch_iterator(cfg, shape, seed=tcfg.seed,
                                      start_step=start_step)
        return state, jitted, batches

    runner = TrainingRunner(directory=args.ckpt_dir, build=build,
                            checkpoint_every=args.ckpt_every)
    t0 = time.time()
    state, history = runner.run(args.steps, inject_fault_at=args.inject_fault_at)
    dt = time.time() - t0
    losses = [h["loss"] for h in history]
    print(f"\ntrained {len(history)} steps in {dt:.1f}s "
          f"({dt / max(len(history), 1):.3f}s/step)")
    print(f"loss: first={losses[0]:.4f} last={losses[-1]:.4f}")
    assert losses[-1] < losses[0], "loss did not decrease"
    print("OK")


if __name__ == "__main__":
    main()
