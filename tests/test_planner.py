"""Planner units: deterministic, monotone plan ranking; scatter-spec /
sanitize-spec layout rules; ZeRO-vs-allreduce trajectory oracle (subprocess:
needs the 8-device CPU mesh)."""
import os
import subprocess
import sys
import warnings

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import configs
from repro.config import ParallelConfig
from repro.core import costmodel
from repro.models import transformer as T
from repro.models.moe import MeshCtx
from repro.parallel import planner
from repro.parallel.sharding import (dropped_partition_report, opt_specs,
                                     param_specs, reset_dropped_partitions,
                                     sanitize_spec, scatter_specs)

PROGS = os.path.join(os.path.dirname(__file__), "progs")
ARCH = "llama3.2-3b"


# ---------------------------------------------------------------------------
# plan_search ranking
# ---------------------------------------------------------------------------
def test_plan_search_deterministic():
    cfg = configs.get(ARCH)
    a = planner.plan_search(cfg, (16, 16), 256, 4096, "train")
    b = planner.plan_search(cfg, (16, 16), 256, 4096, "train")
    assert [r.plan.label() for r in a] == [r.plan.label() for r in b]
    assert [r.total_s for r in a] == [r.total_s for r in b]
    assert a and a[0].feasible, "no feasible plan for the 3B cell"


def test_plan_search_more_hbm_superset():
    """More HBM per chip ⇒ the feasible set only grows (monotone gate)."""
    cfg = configs.get(ARCH)
    small = {r.plan.label() for r in
             planner.plan_search(cfg, (16, 16), 256, 4096, "train",
                                 hbm=8 * 2**30) if r.feasible}
    big = {r.plan.label() for r in
           planner.plan_search(cfg, (16, 16), 256, 4096, "train",
                               hbm=64 * 2**30) if r.feasible}
    assert small <= big
    assert len(big) > len(small)


@pytest.mark.parametrize("p", [4, 16, 64])
def test_zero_beats_allreduce_on_larger_meshes(p):
    """On a pure-DP mesh the zero strategy's predicted comm + optimizer
    traffic undercuts the all-reduce step's for every dp ≥ 4 (the f32 grad
    reduce-scatter moves half the wire bytes of the all-reduce, and the
    redundant full update disappears) — and the gap widens with the mesh."""
    cfg = configs.get(ARCH)
    pc = cfg.param_counts()

    def cost(grad):
        return costmodel.train_step_cost(
            pc["active"], pc["total"], tokens=4096.0 * p, chips=p, tp=1,
            dp=p, fsdp_shard=1, grad=grad, batch_local=1, seq=4096,
            d_model=cfg.d_model, n_layers=cfg.n_layers, grad_bytes=4)

    ar, z = cost("all_reduce"), cost("reduce_scatter_zero")
    assert z["grad_s"] < ar["grad_s"]
    assert z["update_s"] < ar["update_s"]
    assert z["total_s"] < ar["total_s"]
    # the advantage is monotone in the mesh: at 2p the ratio doesn't shrink
    ar2 = costmodel.train_step_cost(
        pc["active"], pc["total"], tokens=4096.0 * 2 * p, chips=2 * p, tp=1,
        dp=2 * p, fsdp_shard=1, grad="all_reduce", batch_local=1, seq=4096,
        d_model=cfg.d_model, n_layers=cfg.n_layers, grad_bytes=4)
    z2 = costmodel.train_step_cost(
        pc["active"], pc["total"], tokens=4096.0 * 2 * p, chips=2 * p, tp=1,
        dp=2 * p, fsdp_shard=1, grad="reduce_scatter_zero", batch_local=1,
        seq=4096, d_model=cfg.d_model, n_layers=cfg.n_layers, grad_bytes=4)
    assert (ar2["update_s"] - z2["update_s"]) >= \
        (ar["update_s"] - z["update_s"]) * 0.99


def test_zero_memory_scales_down_with_dp():
    """ZeRO shards grads + moments over dp: per-device state bytes drop as
    1/dp (ZeRO's Θ(2m/p) vs Θ(2m)); the all-reduce layout stays flat."""
    n = 1e9
    prev = None
    for dp in (2, 4, 8, 16):
        z = costmodel.train_memory_bytes(n, dp=dp, grad="reduce_scatter_zero")
        ar = costmodel.train_memory_bytes(n, dp=dp, grad="all_reduce")
        assert z["opt"] * dp == pytest.approx(ar["opt"])
        assert z["grads"] * dp == pytest.approx(ar["grads"])
        if prev is not None:
            assert z["total"] < prev
        prev = z["total"]


def test_default_plan_properties():
    """The production train cell picks a memory-feasible ZeRO point with
    full remat and f32 moments (the numerics guard), and the serve cell
    reproduces the TP-resident-when-it-fits rule."""
    plan = planner.default_plan(ARCH, "train")
    assert plan.grad == "reduce_scatter_zero"
    assert plan.remat == "full"
    assert plan.opt_state_dtype == "float32"
    pcfg = plan.to_pcfg()
    assert pcfg.grad_reduce == "reduce_scatter_zero"
    # 3B params at bf16 fit one chip's TP shard comfortably: no FSDP gathers
    assert planner.default_plan(ARCH, "decode").fsdp_axes == ()
    # 405B does not: params stay FSDP-sharded for serving
    assert planner.default_plan("llama3-405b", "decode").fsdp_axes


def test_plan_lattice_head_is_runnable_when_nothing_fits():
    """Even when no point fits (405B train on 16 GiB chips at this batch),
    plan_search returns the full lattice ranked with the least-infeasible
    point first — never an empty list."""
    cfg = configs.get("llama3-405b")
    ranked = planner.plan_search(cfg, (16, 16), 256, 4096, "train")
    assert ranked
    mems = [r.memory["total"] for r in ranked if not r.feasible]
    if not ranked[0].feasible:
        assert ranked[0].memory["total"] == min(mems)


# ---------------------------------------------------------------------------
# layout rules
# ---------------------------------------------------------------------------
def _ctx8():
    mesh = AbstractMesh((8, 1), ("data", "model"))
    return MeshCtx(mesh=mesh, batch_axes=("data",), model_axis="model",
                   fsdp_axes=())


def test_scatter_specs_adds_data_axis():
    from repro.launch.train import reduced
    rcfg = reduced(configs.get(ARCH))
    params = jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0), rcfg))
    ctx = _ctx8()
    sspec = scatter_specs(params, rcfg, ctx)
    pspec = param_specs(params, rcfg, ctx)
    flat_s = jax.tree.leaves(sspec, is_leaf=lambda x: isinstance(x, P))
    flat_p = jax.tree.leaves(pspec, is_leaf=lambda x: isinstance(x, P))
    flat_l = jax.tree.leaves(params)
    changed = 0
    for s, p_, leaf in zip(flat_s, flat_p, flat_l):
        if s != p_:
            changed += 1
            # the added partition divides its dim by the scatter group
            parts = tuple(s) + (None,) * (leaf.ndim - len(tuple(s)))
            hit = [i for i, a in enumerate(parts) if a == "data"
                   or (isinstance(a, tuple) and "data" in a)]
            assert hit and leaf.shape[hit[0]] % 8 == 0, (s, leaf.shape)
    assert changed > 0


def test_scatter_specs_noop_on_fsdp_sharded_leaves():
    """FSDP param storage already scatters the matrix leaves — the ZeRO
    layout must not double-shard those; only the FSDP-replicated stragglers
    (norm scales, biases) gain a scatter axis."""
    from repro.launch.train import reduced
    rcfg = reduced(configs.get(ARCH))
    params = jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0), rcfg))
    mesh = AbstractMesh((8, 1), ("data", "model"))
    ctx = MeshCtx(mesh=mesh, batch_axes=("data",), model_axis="model",
                  fsdp_axes=("data",))
    sspec = scatter_specs(params, rcfg, ctx)
    pspec = param_specs(params, rcfg, ctx)
    is_p = lambda x: isinstance(x, P)
    for s, p_ in zip(jax.tree.leaves(sspec, is_leaf=is_p),
                     jax.tree.leaves(pspec, is_leaf=is_p)):
        had_data = any(a == "data" or (isinstance(a, tuple) and "data" in a)
                       for a in tuple(p_))
        if had_data:
            assert s == p_, (s, p_)


def test_opt_specs_scatter_layout():
    pspec = {"w": P(None, "model")}
    sspec = {"w": P("data", "model")}
    assert opt_specs(pspec)["m"] is pspec
    o = opt_specs(pspec, sspec)
    assert o["m"] is sspec and o["v"] is sspec and o["step"] == P()


def test_sanitize_spec_reports_dropped_partitions():
    mesh = AbstractMesh((8, 1), ("data", "model"))
    reset_dropped_partitions()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        kept = sanitize_spec(P("data"), (64,), mesh, path="ok/leaf")
        dropped = sanitize_spec(P("data"), (7,), mesh, path="bad/leaf")
    assert kept == P("data") and dropped == P(None)
    rep = dropped_partition_report()
    assert [r["leaf"] for r in rep] == ["bad/leaf"]
    assert rep[0]["axes"] == ("data",) and rep[0]["shard"] == 8
    reset_dropped_partitions()
    assert dropped_partition_report() == []


# ---------------------------------------------------------------------------
# trajectory oracle (8-device subprocess)
# ---------------------------------------------------------------------------
def test_zero_step_matches_allreduce_trajectory():
    """make_train_step_zero ≡ make_train_step on a 1×8 CPU mesh: loss
    trajectory bit-for-bit in f32, params to layout-ulps, moments stored as
    1/8 shards."""
    r = subprocess.run([sys.executable, os.path.join(PROGS, "zero_step_prog.py")],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    assert "ZERO_OK" in r.stdout
