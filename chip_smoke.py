"""On-chip smoke run: the serving path at chatglm3-6b's published widths.

  python chip_smoke.py [--seed N]      # one TPU chip
  python chip_smoke.py --chips 4       # four chips: distributed matmul + ZeRO

One chip (no ``--chips``), all in this one process:
  * init     -- chatglm3-6b at its published widths (28 layers, d_model 4096,
                vocab 65024), random bf16 weights drawn from ``--seed`` on the
                device by the serving entry point (``serve.serving_params``);
  * paged    -- 8 requests of mixed prompt lengths (64-512 tokens, 32
                generated each) through ``Scheduler(paged=True)``, cold
                (compile included) and again warm;
  * steps    -- the same prompts through ``make_chunk_prefill_step`` and the
                logits-returning paged ``make_decode_step``: every logit is
                finite, the first tokens match the scheduler's, and the
                compiled decode step holds the Pallas kernel
                (``tpu_custom_call``);
  * aligned  -- 4 requests through the default end-aligned engine on the
                same weights;
  * kernel   -- ``paged_attention`` (Pallas) against ``ref.paged_attention``
                at the model's decode shapes, within ``KERNEL_TOL``;
  * oracle   -- the paged-vs-end-aligned token-identity oracle of
                ``tests/test_paged.py`` at reduced width (f32, highest
                matmul precision);
  * train    -- a few train steps at reduced width; the loss must fall.  At
                full width the f32 AdamW state of 6.24 B parameters (about
                100 GB) does not fit one 16 GB chip.

``--chips 4`` runs only what exists across chips: 2x2 SUMMA and Cannon with
the compiled Pallas ``matmul_acc`` at n=8192 bf16 against one single-device
``jnp.matmul``, and the ZeRO reduce-scatter train step on a (4, 1) data mesh
against the all-reduce step.

Lines starting ``[smoke]`` are smoke timings (host wall seconds after
``block_until_ready``; "cold" includes compilation) and the device's
``peak_bytes_in_use`` -- not benchmark numbers.  The last line is one JSON
object naming the device.  Without a TPU the script exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "chatglm3-6b"
SLOTS, BLOCK, CHUNK, GEN = 8, 16, 128, 32
PROMPTS = (64, 448, 96, 512, 128, 320, 200, 256)   # paged engine's mix
ALIGNED_PROMPTS = (64, 200, 320, 512)              # end-aligned engine's
# bf16 kernel output vs the f32 reference: a few bf16 ulps at unit scale
# (probabilities stay f32 inside the kernel; the jnp reference rounds them
# to bf16 before the PV contraction, as the model's own _sdpa does)
KERNEL_TOL = 2e-2
MATMUL_RTOL = 1e-3          # max |C - ref| / max |ref| (f32 accumulation)
ZERO_RTOL = 1e-5            # ZeRO vs all-reduce losses and params (f32)


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def peak_bytes(jax) -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_phase(jax, name: str, fn, *args) -> object:
    t0 = time.perf_counter()
    result, note = fn(*args)
    jax.block_until_ready(result)
    dt = time.perf_counter() - t0
    print(f"[smoke] {name}: {dt:.3f} s, peak_bytes_in_use="
          f"{peak_bytes(jax)}{'; ' + note if note else ''}", flush=True)
    return result


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------
def make_reqs(prompts, vocab: int, seed: int):
    import numpy as np
    from repro.launch.scheduler import Request
    rng = np.random.RandomState(seed)
    return [Request(rid=i, prompt=rng.randint(0, vocab, (lp,)).astype(np.int32),
                    gen=GEN, arrival=2 * i)
            for i, lp in enumerate(prompts)]


def serving_plan():
    from repro.parallel import planner
    return planner.ParallelPlan(mesh_shape=(1, 1), fsdp_axes=(), tp=1,
                                grad="none", remat="none")


def phase_init(cfg, seed: int):
    import jax
    from repro.launch.serve import serving_params
    cfg, params = serving_params(cfg, seed)
    leaves = jax.tree.leaves(params)
    check(all(x.dtype == cfg.dtype for x in leaves),
          "serving weights are not all in the compute dtype")
    nbytes = sum(x.size * x.dtype.itemsize for x in leaves)
    return (cfg, params), (f"{cfg.name}: {cfg.n_layers} layers, d_model "
                           f"{cfg.d_model}, vocab {cfg.vocab}, "
                           f"{sum(x.size for x in leaves)} params, "
                           f"{nbytes} bytes")


def check_served(out, reqs, vocab: int, engine: str) -> None:
    comps = out["completions"]
    check(sorted(comps) == [r.rid for r in reqs],
          f"{engine}: not every request completed")
    for r in reqs:
        toks = comps[r.rid].tokens
        check(len(toks) == r.gen and all(0 <= t < vocab for t in toks),
              f"{engine}: request {r.rid} returned {toks}")


def phase_paged(cfg, params, reqs):
    from repro.launch.scheduler import Scheduler
    sched = Scheduler(cfg, serving_plan(), params, slots=SLOTS,
                      max_len=max(PROMPTS) + GEN, paged=True, block=BLOCK,
                      chunk=CHUNK)
    t0 = time.perf_counter()
    cold = sched.run(reqs)
    t_cold = time.perf_counter() - t0
    sched.reset()
    warm = sched.run(reqs)
    for out in (cold, warm):
        check_served(out, reqs, cfg.vocab, "paged")
    check(all(cold["completions"][r.rid].tokens == warm["completions"][r.rid].tokens
              for r in reqs), "paged: warm run's tokens differ from cold run's")
    return warm["completions"], (
        f"cold run {t_cold:.3f} s (compile included), warm run "
        f"{warm['wall_s']:.3f} s for {warm['generated']} tokens in "
        f"{warm['ticks']} ticks; pool peak occupancy "
        f"{warm['pool']['peak_occupancy']:.3f}")


def phase_steps(cfg, params, reqs, comps):
    """The paged engine's two step programs, driven directly: finite logits,
    first tokens equal to the scheduler's, and the kernel in the program."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as T
    from repro.parallel import steps as S
    from repro.serving import BlockPool
    plan = serving_plan().to_pcfg()
    n_pages = -(-(max(PROMPTS) + GEN) // BLOCK)
    pool = BlockPool(SLOTS * n_pages, BLOCK)
    cache = T.init_paged_cache(cfg, pool.n_blocks, BLOCK)
    prefill = jax.jit(S.make_chunk_prefill_step(cfg, plan, None),
                      donate_argnums=(2,))
    tables = np.full((SLOTS, n_pages), -1, np.int32)
    first = np.zeros((SLOTS,), np.int32)
    pos = np.zeros((SLOTS,), np.int32)
    for i, r in enumerate(reqs):
        lp = len(r.prompt)
        pool.admit(r.rid, lp + r.gen)
        pool.ensure(r.rid, lp + 1)
        tables[i] = pool.table(r.rid, n_pages)
        for lo in range(0, lp, CHUNK):
            ln = min(CHUNK, lp - lo)
            toks = np.zeros((1, CHUNK), np.int32)
            toks[0, :ln] = r.prompt[lo:lo + ln]
            logits, cache = prefill(params, jnp.asarray(toks), cache,
                                    jnp.int32(lo), jnp.asarray(tables[i:i + 1]),
                                    jnp.int32(ln))
        check(bool(jnp.all(jnp.isfinite(logits))),
              f"prefill logits of request {r.rid} are not finite")
        first[i], pos[i] = int(jnp.argmax(logits[0])), lp
    want = [comps[r.rid].tokens[0] for r in reqs]
    check(first.tolist() == want,
          f"step-built first tokens {first.tolist()} != scheduler's {want}")
    args = (params, jnp.asarray(first), cache, jnp.asarray(pos),
            jnp.asarray(tables))
    decode = jax.jit(S.make_decode_step(cfg, plan, None, return_logits=True,
                                        paged=True), donate_argnums=(2,))
    t0 = time.perf_counter()
    compiled = decode.lower(*args).compile()
    t_compile = time.perf_counter() - t0
    check("tpu_custom_call" in compiled.as_text(),
          "the paged decode step holds no Pallas kernel (tpu_custom_call)")
    logits, _ = compiled(*args)
    check(logits.shape == (SLOTS, cfg.vocab)
          and bool(jnp.all(jnp.isfinite(logits))),
          "paged decode logits are not finite")
    return logits, (f"decode step compile {t_compile:.3f} s; logits "
                    f"{logits.shape} finite; tpu_custom_call present")


def phase_aligned(cfg, params, reqs, paged_comps):
    from repro.launch.scheduler import Scheduler
    sched = Scheduler(cfg, serving_plan(), params, slots=len(reqs),
                      max_len=max(ALIGNED_PROMPTS) + GEN, bucket=256)
    out = sched.run(reqs)
    check_served(out, reqs, cfg.vocab, "end-aligned")
    # the same requests went through the paged engine; bf16 rounding may
    # legitimately split the two engines, so agreement is reported only
    agree = sum(out["completions"][r.rid].tokens[0]
                == paged_comps[r.rid].tokens[0] for r in reqs)
    return out["generated"], (f"{out['generated']} tokens in "
                              f"{out['wall_s']:.3f} s (compile included); "
                              f"first-token agreement with paged: "
                              f"{agree}/{len(reqs)}")


def phase_kernel(cfg, seed: int):
    """Pallas paged attention vs the jnp reference at the decode shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    n_pages = -(-(max(PROMPTS) + GEN) // BLOCK)
    n_blocks = SLOTS * n_pages
    hkv, rep, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd
    rng = np.random.RandomState(seed)
    lengths = np.asarray([1, BLOCK, BLOCK + 1] + list(PROMPTS[3:]), np.int32)
    tables = np.full((SLOTS, n_pages), -1, np.int32)
    perm, used = rng.permutation(n_blocks), 0
    for row, ln in enumerate(lengths):
        chain = -(-int(ln) // BLOCK)
        tables[row, :chain] = perm[used:used + chain]
        used += chain
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (SLOTS, hkv, rep, hd), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (n_blocks, hkv, BLOCK, hd), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (n_blocks, hkv, BLOCK, hd), jnp.bfloat16)
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    got = jax.jit(ops.paged_attention)(q, kp, vp, tables, lengths)
    ref_bf16 = jax.jit(ref.paged_attention)(q, kp, vp, tables, lengths)
    with jax.default_matmul_precision("highest"):
        f32 = lambda x: x.astype(jnp.float32)
        truth = jax.jit(ref.paged_attention)(f32(q), f32(kp), f32(vp),
                                             tables, lengths)
    err = float(jnp.max(jnp.abs(f32(got) - truth)))
    err_ref = float(jnp.max(jnp.abs(f32(ref_bf16) - truth)))
    err_pair = float(jnp.max(jnp.abs(f32(got) - f32(ref_bf16))))
    check(bool(jnp.all(jnp.isfinite(got))), "kernel output is not finite")
    check(err <= KERNEL_TOL and err_pair <= KERNEL_TOL,
          f"kernel vs reference: max abs err {err} (vs f32), {err_pair} (vs "
          f"bf16 ref) > {KERNEL_TOL}")
    return got, (f"B={SLOTS} Hkv={hkv} rep={rep} hd={hd} block={BLOCK} "
                 f"pages={n_pages}: max abs err kernel-vs-f32 {err}, "
                 f"bf16-ref-vs-f32 {err_ref}, kernel-vs-bf16-ref {err_pair} "
                 f"(tol {KERNEL_TOL})")


def phase_oracle():
    import jax
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import test_paged
    from repro.models import transformer as T
    cfg = test_paged.tiny()
    with jax.default_matmul_precision("highest"):
        test_paged.test_paged_tokens_identical_to_end_aligned(
            (cfg, T.init(jax.random.PRNGKey(0), cfg)))
    return None, f"{cfg.name} reduced (d_model {cfg.d_model}): tokens identical"


def phase_train(arch_cfg, seed: int, steps: int = 8):
    import jax
    import numpy as np
    from repro.config import ParallelConfig, ShapeConfig, TrainConfig
    from repro.data import make_batch_iterator
    from repro.launch.train import reduced
    from repro.parallel import steps as S
    cfg = reduced(arch_cfg)
    pcfg = ParallelConfig(remat="none", fsdp_params=False)
    tcfg = TrainConfig(lr=3e-3, warmup_steps=2, total_steps=20, z_loss=0.0,
                       seed=seed)
    state = S.init_train_state(jax.random.PRNGKey(seed), cfg, pcfg)
    step = jax.jit(S.make_train_step(cfg, pcfg, tcfg, None),
                   donate_argnums=(0,))
    batches = make_batch_iterator(cfg, ShapeConfig("smoke", "train", 128, 8),
                                  seed=seed)
    losses = []
    for _, batch in zip(range(steps), batches):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    check(bool(np.all(np.isfinite(losses))) and losses[-1] < losses[0],
          f"train loss did not fall: {losses}")
    return state, (f"{cfg.name} reduced (d_model {cfg.d_model}): loss "
                   f"{losses[0]} -> {losses[-1]} over {steps} steps")


def one_chip(seed: int) -> None:
    import jax
    from repro import configs
    cfg, params = run_phase(jax, "init", phase_init, configs.get(ARCH), seed)
    reqs = make_reqs(PROMPTS, cfg.vocab, seed)
    comps = run_phase(jax, "paged", phase_paged, cfg, params, reqs)
    run_phase(jax, "steps", phase_steps, cfg, params, reqs, comps)
    aligned = [r for r in reqs if len(r.prompt) in ALIGNED_PROMPTS]
    run_phase(jax, "aligned", phase_aligned, cfg, params, aligned, comps)
    del params
    run_phase(jax, "kernel", phase_kernel, cfg, seed)
    run_phase(jax, "oracle", phase_oracle)
    run_phase(jax, "train", phase_train, configs.get(ARCH), seed)


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------
def phase_matmul(name: str, fn, n: int, seed: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import make_grid_mesh
    mesh = make_grid_mesh((2, 2))
    grid = NamedSharding(mesh, P("x", "y"))
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    gen = jax.jit(lambda k: jax.random.normal(k, (n, n), jnp.bfloat16),
                  out_shardings=grid)
    a, b = gen(ka), gen(kb)
    t0 = time.perf_counter()
    compiled = jax.jit(lambda x, y: fn(x, y, mesh)).lower(a, b).compile()
    t_compile = time.perf_counter() - t0
    text = compiled.as_text()
    colls = [c for c in ("collective-permute", "all-gather", "all-reduce",
                         "reduce-scatter", "all-to-all") if c in text]
    check("tpu_custom_call" in text, f"{name}: no Pallas kernel in program")
    check(bool(colls), f"{name}: no collective in the compiled program")
    c = jax.block_until_ready(compiled(a, b))
    t0 = time.perf_counter()
    c = jax.block_until_ready(compiled(a, b))
    t_warm = time.perf_counter() - t0
    check(len(c.sharding.device_set) == 4,
          f"{name}: output spans {len(c.sharding.device_set)} devices")
    one = jax.devices()[0]
    want = jax.jit(lambda x, y: jnp.matmul(
        x, y, preferred_element_type=jnp.float32))(
        jax.device_put(a, one), jax.device_put(b, one))
    err = float(jnp.max(jnp.abs(jax.device_put(c, one) - want))
                / jnp.max(jnp.abs(want)))
    check(err <= MATMUL_RTOL, f"{name}: rel err {err} > {MATMUL_RTOL}")
    return c, (f"n={n} bf16 on 2x2: compile {t_compile:.3f} s, warm call "
               f"{t_warm:.6f} s; collectives {colls}; rel err vs "
               f"single-device jnp.matmul {err} (tol {MATMUL_RTOL})")


def phase_zero(seed: int, steps: int = 4):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.config import ParallelConfig, ShapeConfig, TrainConfig
    from repro.core.compat import make_mesh
    from repro.data import make_batch_iterator
    from repro.launch.train import reduced
    from repro.parallel import steps as S
    from repro.parallel.sharding import make_ctx
    cfg = reduced(configs.get(ARCH)).replace(dtype="float32",
                                             param_dtype="float32")
    mesh = make_mesh((4, 1), ("data", "model"))
    tcfg = TrainConfig(lr=3e-3, warmup_steps=2, total_steps=20, z_loss=0.0)
    bsh = {"tokens": NamedSharding(mesh, P(("data",), None))}

    def run(grad):
        pcfg = ParallelConfig(remat="none", fsdp_params=False,
                              grad_dtype="float32", grad_reduce=grad)
        ctx = make_ctx(mesh, pcfg)
        state = S.init_train_state(jax.random.PRNGKey(seed), cfg, pcfg)
        sh = S.train_state_shardings(cfg, pcfg, ctx, state)
        state = jax.device_put(state, sh)
        step = jax.jit(S.make_train_step(cfg, pcfg, tcfg, ctx),
                       in_shardings=(sh, bsh), out_shardings=(sh, None),
                       donate_argnums=(0,))
        batches = make_batch_iterator(cfg, ShapeConfig("t", "train", 64, 8),
                                      seed=seed)
        losses, text = [], None
        for _, batch in zip(range(steps), batches):
            batch = jax.device_put(batch, bsh)
            if text is None:
                text = step.lower(state, batch).compile().as_text()
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        return np.asarray(losses), state, text

    with jax.default_matmul_precision("highest"):
        l_ar, s_ar, _ = run("all_reduce")
        l_z, s_z, text = run("reduce_scatter_zero")
    check(np.allclose(l_z, l_ar, rtol=ZERO_RTOL, atol=0),
          f"ZeRO losses {l_z.tolist()} != all-reduce {l_ar.tolist()}")
    check(l_ar[-1] < l_ar[0], f"loss did not fall: {l_ar.tolist()}")
    pdiff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                for a, b in zip(jax.tree.leaves(s_ar["params"]),
                                jax.tree.leaves(s_z["params"])))
    check(pdiff <= ZERO_RTOL, f"ZeRO params differ by {pdiff}")
    colls = [c for c in ("reduce-scatter", "all-gather", "all-reduce")
             if c in text]
    check("all-gather" in text, f"ZeRO step gathers no params: {colls}")
    leaves = jax.tree.leaves(s_z["opt"]["m"])
    check(all(len(x.sharding.device_set) == 4 for x in leaves),
          "ZeRO moments do not span 4 devices")
    scattered = sum(x.addressable_shards[0].data.size * 4 == x.size
                    for x in leaves)
    check(scattered > 0, "no optimizer moment is stored as a 1/4 shard")
    return s_z, (f"{cfg.name} reduced on (4,1): losses {l_z.tolist()} vs "
                 f"all-reduce {l_ar.tolist()}; max param diff {pdiff}; "
                 f"collectives {colls}; {scattered}/{len(leaves)} moments "
                 f"stored as 1/4 shards")


def four_chips(seed: int) -> None:
    import jax
    from repro.core import cannon_matmul_pallas, summa_matmul_pallas
    run_phase(jax, "summa", phase_matmul, "summa", summa_matmul_pallas, 8192,
              seed)
    run_phase(jax, "cannon", phase_matmul, "cannon", cannon_matmul_pallas,
              8192, seed)
    run_phase(jax, "zero", phase_zero, seed)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every weight, prompt and input")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phases")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {d.platform!r} "
             f"({d.device_kind}, {len(devs)} device(s))")
    if len(devs) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, found "
             f"{len(devs)}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    print(f"[smoke] device: {d.platform} {d.device_kind} x{len(devs)}",
          flush=True)
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
