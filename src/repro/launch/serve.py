"""Serving launcher: thin CLI over the continuous-batching scheduler.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --requests 4 \
      --prompt-len 32 --gen 16 --slots 4 --stagger 2

Serves a reduced config by default (CPU-sized); ``--no-reduce`` serves the
published widths (e.g. chatglm3-6b on one 16 GB TPU v5e chip).  Weights are
random, drawn from ``--seed`` directly in the compute dtype on the device
(``serving_params``): no f32 master copy exists on the serve path.
Each prompt is prefilled in ONE fused cache-writing forward (recurrent
families fall back to a per-token loop), then requests share a fixed slot
pool: staggered arrivals are admitted into free slots mid-flight, finished
requests evicted, greedy tokens streamed per request
(``launch/scheduler.py``).  ``--naive`` serves one request at a time
(slots=1) for an A/B against the batched engine.  ``--paged`` switches to
the paged KV-cache engine (``serving/kvcache.py``): admission becomes
chunked prefill (``--chunk`` tokens per tick) writing into ``--block``-token
pages of a shared arena, request length is bounded by pool capacity instead
of the per-slot row, and the end-of-run report includes the pool's
occupancy / fragmentation.  A warmup pass runs first so JIT compile time
never lands in the reported tok/s, and every timing reads after
``jax.block_until_ready``.
"""
from __future__ import annotations

import argparse

import jax

from repro import configs
from repro.config import ModelConfig
from repro.launch.cache import use_compile_cache
from repro.launch.scheduler import Scheduler, make_requests
from repro.launch.train import reduced
from repro.models import transformer as T
from repro.parallel import planner


def serving_params(cfg: ModelConfig, seed: int):
    """Random serving weights drawn from ``seed``, created directly in the
    compute dtype (``cfg.dtype``) by the jitted init on the default device.
    Returns ``(serving_cfg, params)``; the config records the param dtype."""
    cfg = cfg.replace(param_dtype=cfg.dtype)
    return cfg, T.init(jax.random.PRNGKey(seed), cfg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--reduce", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the CPU-sized reduced config (default); "
                         "--no-reduce serves the published widths")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--stagger", type=int, default=2,
                    help="ticks (decode steps) between request arrivals")
    ap.add_argument("--naive", action="store_true",
                    help="one-request-at-a-time baseline (slots=1)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV-cache engine: block-pool arena + chunked "
                         "prefill admission (pure-attention archs)")
    ap.add_argument("--block", type=int, default=16,
                    help="page size in tokens (only with --paged)")
    ap.add_argument("--chunk", type=int, default=32,
                    help="prefill tokens consumed per tick (only with --paged)")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="total pages in the pool (default: slots x "
                         "ceil(max_len/block), the end-aligned memory)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature; 0 = greedy (the default "
                         "and the test oracle)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (only with --temperature > 0)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the sampling "
                         "stream (reproducible runs)")
    args = ap.parse_args()
    if args.requests < 1 or args.gen < 1:
        ap.error(f"--requests and --gen must be >= 1 "
                 f"(got {args.requests}/{args.gen})")
    if args.prompt_len < 0 or args.slots < 1 or args.stagger < 0:
        ap.error("--prompt-len/--stagger must be >= 0 and --slots >= 1")
    if args.block < 1 or args.chunk < 1 or \
            (args.pool_blocks is not None and args.pool_blocks < 1):
        ap.error("--block/--chunk/--pool-blocks must be >= 1")
    if args.temperature < 0 or not 0 < args.top_p <= 1:
        ap.error("--temperature must be >= 0 and --top-p in (0, 1]")
    if args.prompt_len + args.gen < 2:
        ap.error("--prompt-len + --gen must be >= 2 (the slot pool needs a "
                 "cache of at least two positions)")

    use_compile_cache()
    cfg = configs.get(args.arch)
    if args.reduce:
        cfg = reduced(cfg)
    if cfg.enc_dec:
        raise SystemExit("enc-dec serving: use examples/whisper_serve.py")
    if args.paged and not T.supports_paged(cfg):
        raise SystemExit(f"--paged needs a pure-attention no-SWA arch; "
                         f"{cfg.name} has pattern {cfg.block_pattern} "
                         f"(window={cfg.window})")
    # single-device layout as a first-class plan (the scheduler bridges it)
    plan = planner.ParallelPlan(mesh_shape=(1, 1), fsdp_axes=(), tp=1,
                                grad="none", remat="none")
    cfg, params = serving_params(cfg, args.seed)

    slots = 1 if args.naive else args.slots
    max_len = args.prompt_len + args.gen
    if not args.paged and cfg.window is not None and max_len > cfg.window:
        raise SystemExit(f"prompt+gen {max_len} exceeds the "
                         f"attention window {cfg.window} (end-aligned slots; "
                         f"--paged lifts the limit for no-SWA archs)")
    sched = Scheduler(cfg, plan, params, slots=slots, max_len=max_len,
                      temperature=args.temperature, top_p=args.top_p,
                      seed=args.seed, paged=args.paged, block=args.block,
                      chunk=args.chunk, pool_blocks=args.pool_blocks)

    # warmup: compile prefill/decode/insert outside the timed run
    sched.run(make_requests(min(2, args.requests), args.prompt_len,
                            min(2, args.gen), cfg.vocab))
    sched.reset()

    reqs = make_requests(args.requests, args.prompt_len, args.gen, cfg.vocab,
                         stagger=args.stagger)
    out = sched.run(reqs)
    comps = out["completions"]
    assert len(comps) == args.requests, (len(comps), args.requests)
    mode = "naive (1 slot)" if args.naive else f"batched ({slots} slots)"
    if args.paged:
        mode += f", paged (block={args.block} chunk={args.chunk} " \
                f"pool={sched.pool.n_blocks})"
    if args.temperature > 0:
        mode += f", T={args.temperature} top_p={args.top_p}"
    ttft = sorted(c.ttft_s for c in comps.values())
    print(f"served {args.requests} requests [{mode}, fused_prefill="
          f"{sched.fused}]: {out['generated']} toks in {out['wall_s']:.2f}s "
          f"({out['tok_s']:.1f} tok/s, {out['ticks']} ticks)")
    # nearest-rank percentiles; a p99 of fewer than 100 samples is the max
    pcts = (50, 90, 99) if len(ttft) >= 100 else (50, 90)
    ms = [ttft[-(-len(ttft) * p // 100) - 1] * 1e3 for p in pcts]
    print(f"ttft (due->first token) {'/'.join(f'p{p}' for p in pcts)}: "
          f"{'/'.join(f'{v:.1f}' for v in ms)} ms")
    if args.paged:
        rep = out["pool"]
        print(f"pool: {rep['n_blocks']} blocks x {rep['block']} toks, peak "
              f"occupancy {rep['peak_occupancy']:.2f}, end occupancy "
              f"{rep['occupancy']:.2f}, internal fragmentation at peak "
              f"{rep['frag_at_peak']:.2f}")
    print("sample:", comps[0].tokens[:12])


if __name__ == "__main__":
    main()
