"""The knee of a traffic mix, in engine ticks, on the CPU.

  JAX_PLATFORMS=cpu python3 benchmarks/chip/knee.py --traffic chat \
      --every 4.8,4.9,5.0,5.1,6.25 --seeds 2 --pool 4096

The program takes arrivals in engine ticks, and what it admits in a tick
depends only on the lengths, the slots, the page pool and the chunk: not on
the widths of the model or the speed of the chip.  So the rate at which the
queue starts to grow is found here, with the program's own paged
``Scheduler`` driving a model two layers deep and 64 wide, over the traffic
file's request stream.  For each interval it prints, per seed, the 90th
percentile of the ticks from arrival to admission for the arrivals of the
first and of the last third of the stream (a knee shows as the later wait
outgrowing the earlier).  Nothing
here runs on the chip or is timed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

TINY = {"name": "knee", "family": "dense", "n_layers": 2, "d_model": 64,
        "n_heads": 2, "n_kv_heads": 1, "d_ff": 128, "vocab": 256,
        "rope_fraction": 0.5, "rope_theta": 10000.0, "norm": "rmsnorm",
        "act": "swiglu", "norm_eps": 1e-5, "qk_norm": False,
        "tie_embeddings": False, "dtype": "bfloat16",
        "param_dtype": "bfloat16"}


def waits(traffic: dict, every: float, seed: int, pool: int,
          span: int) -> dict:
    """Queue waits in ticks for the arrivals of ``span`` ticks."""
    from repro.config import ModelConfig
    from repro.launch.scheduler import Request, Scheduler
    from repro.launch.serve import serving_params
    from repro.parallel import planner

    from chipbench import traffic as traffic_mod

    tr = dict(traffic, arrival_every_ticks=every)
    cfg, params = serving_params(ModelConfig(**TINY), seed % 2 ** 32)
    plan = planner.ParallelPlan(mesh_shape=(1, 1), fsdp_axes=(), tp=1,
                                grad="none", remat="none")
    sched = Scheduler(cfg, plan, params, slots=tr["slots"],
                      max_len=traffic_mod.max_len(tr), paged=True,
                      block=tr["block"], chunk=tr["chunk"], pool_blocks=pool)
    stream = traffic_mod.make_stream(tr, seed, cfg.vocab,
                                     int(span / every) + 1, Request)
    out = sched.run(stream.requests)
    steady = [c for rid, c in out["completions"].items()
              if rid not in stream.cohort]
    third = span / 3

    def p90(cs):
        w = [c.admitted_tick - c.arrival for c in cs]
        return float(np.percentile(w, 90)) if w else None

    t0 = stream.first_tick
    return {"every": every, "seed": seed,
            "wait_p90_first_third": p90(
                [c for c in steady if c.arrival < t0 + third]),
            "wait_p90_last_third": p90(
                [c for c in steady if c.arrival >= t0 + 2 * third])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--every", required=True,
                    help="comma-separated arrival intervals in ticks")
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--pool", type=int, required=True,
                    help="the cell's page pool")
    ap.add_argument("--span", type=int, default=1500,
                    help="ticks over which requests arrive")
    args = ap.parse_args(argv)
    traffic = json.loads((HERE / "traffic" / f"{args.traffic}.json")
                         .read_text())
    for every in (float(x) for x in args.every.split(",")):
        for s in range(args.seeds):
            print(json.dumps(waits(traffic, every, 2 ** 31 + s, args.pool,
                                   args.span)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
