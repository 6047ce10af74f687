"""End-to-end behaviour tests: training convergence, serving, sharding rules,
dry-run cell construction."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.config import ParallelConfig, ShapeConfig, TrainConfig, SHAPES
from repro.data import make_batch_iterator
from repro.launch.train import reduced
from repro.parallel import steps as S
from repro.models import transformer as T


def test_training_loss_decreases():
    """30 steps on the structured synthetic stream must cut the loss well
    below the start (the every-token-repeated rule is learnable)."""
    cfg = reduced(configs.get("llama3.2-3b")).replace(vocab=64)
    pcfg = ParallelConfig(remat="none", fsdp_params=False)
    tcfg = TrainConfig(lr=3e-3, warmup_steps=5, total_steps=40, z_loss=0.0)
    shape = ShapeConfig("t", "train", 64, 4)
    step = jax.jit(S.make_train_step(cfg, pcfg, tcfg, None), donate_argnums=(0,))
    state = S.init_train_state(jax.random.PRNGKey(0), cfg, pcfg)
    losses = []
    it = make_batch_iterator(cfg, shape)
    for i, batch in zip(range(30), it):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[:3] + losses[-3:]


def test_serve_loop_greedy_decode():
    cfg = reduced(configs.get("chatglm3-6b"))
    pcfg = ParallelConfig(remat="none", fsdp_params=False)
    params = T.init(jax.random.PRNGKey(0), cfg)
    decode = jax.jit(S.make_decode_step(cfg, pcfg, None), donate_argnums=(2,))
    b, n = 2, 8
    cache = T.init_cache(cfg, b, n)
    tok = jnp.zeros((b,), jnp.int32)
    outs = []
    for i in range(n):
        tok, cache = decode(params, tok, cache, jnp.int32(i))
        outs.append(np.asarray(tok))
    assert all(o.shape == (b,) for o in outs)
    assert all((o >= 0).all() and (o < cfg.vocab).all() for o in outs)


def test_param_spec_rules_cover_all_archs():
    """Every arch's full-size param tree gets a valid, divisible spec on the
    production mesh (structural check — no allocation)."""
    from repro.parallel.sharding import param_specs
    from repro.models.moe import MeshCtx
    from repro.models import encdec as E
    from jax.sharding import AbstractMesh
    from jax.sharding import PartitionSpec as P

    mesh = AbstractMesh((16, 16), ("data", "model"))
    for arch in configs.ARCHS:
        cfg = configs.get(arch)
        init = E.init if cfg.enc_dec else T.init
        params = jax.eval_shape(lambda init=init, cfg=cfg:
                                init(jax.random.PRNGKey(0), cfg))
        ctx = MeshCtx(mesh=mesh, batch_axes=("data",), model_axis="model",
                      fsdp_axes=("data",))
        specs = param_specs(params, cfg, ctx)
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_p) == len(flat_s)
        for leaf, spec in zip(flat_p, flat_s):
            for dim, part in zip(leaf.shape, tuple(spec) + (None,) * 9):
                if part is None:
                    continue
                axes = part if isinstance(part, tuple) else (part,)
                size = int(np.prod([mesh.shape[a] for a in axes]))
                assert dim % size == 0, (arch, leaf.shape, spec)


def test_build_cell_all_40():
    """All 40 (arch × shape) cells construct abstract inputs + shardings."""
    from repro.launch.specs import build_cell
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((16, 16), ("data", "model"))
    n = 0
    for arch, shape_name, skip in configs.cells():
        n += 1
        if skip:
            continue
        cfg = configs.get(arch)
        cell = build_cell(cfg, SHAPES[shape_name], mesh, ParallelConfig())
        assert cell.abstract_args
    assert n == 40


@pytest.mark.slow
def test_train_launcher_with_fault_injection():
    """The CLI driver completes despite an injected node failure."""
    import shutil
    shutil.rmtree("/tmp/repro_test_fault", ignore_errors=True)  # no stale resume
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "chatglm3-6b",
         "--steps", "8", "--batch", "2", "--seq", "64", "--ckpt-every", "3",
         "--ckpt-dir", "/tmp/repro_test_fault", "--inject-fault-at", "5"],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=__import__("os").path.join(__import__("os").path.dirname(__file__), ".."))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "OK" in r.stdout


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR, when set, places the persistent cache and
    the entry points set no directory of their own; otherwise the cache goes
    to one fixed, git-ignored directory inside the checkout."""
    from repro.launch import cache
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "before"))
    try:
        cache.use_compile_cache()
        got = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    if from_env:
        assert got == str(tmp_path / "before")
    else:
        root = cache.REPO_CACHE_DIR.parent
        assert got == str(cache.REPO_CACHE_DIR)
        assert (root / "chip_smoke.py").exists()
        ignored = (root / ".gitignore").read_text().split()
        assert cache.REPO_CACHE_DIR.name + "/" in ignored
