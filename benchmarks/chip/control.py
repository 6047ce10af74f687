"""Readings that set a cell's limit, on the chip, many seeds in one process.

  python3 benchmarks/chip/control.py --workload chatglm3-6b.chat \
      --seeds 11,12,13 --seconds 20 [--control]

For each seed: one run of the cell (a short window at the cell's own load,
set-up and reference as in ``bench.py``), then the program's widest gap;
with ``--control`` also the control's: the reference computed with float8
weight products, read at the same positions of the same prompts and served
tokens.  One JSON line per seed, and the lower and upper readings at the
end.  The benchmark's own runs never run the control.  Without a TPU it
exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    from chipbench import spec
    cell = spec.load_cell(args.workload)

    import jax
    from repro.launch.cache import use_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"control: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    use_compile_cache()

    from chipbench import correct
    from chipbench.cell import run_cell, weight_seed

    ref = spec.reference(cell)
    program, control = [], []
    for seed in seeds:
        t0 = time.perf_counter()
        res = run_cell(cell, seed, args.seconds, False, t_start=t0,
                       log=lambda s: print(s, file=sys.stderr, flush=True))
        line = {"seed": seed,
                "program_widest_gap": res.compared["widest_gap"][0],
                "program_gaps": res.gaps,
                "requests": len(res.seqs),
                "served_tokens": sum(len(s) for _, s in res.seqs)}
        program.append(line["program_widest_gap"])
        if args.control:
            t1 = time.perf_counter()
            gaps = correct.control_token_gaps(ref, cell.model,
                                              weight_seed(seed), res.seqs)
            line["control_gaps"] = [float(g.max()) for g in gaps]
            line["control_widest_gap"] = max(line["control_gaps"])
            line["control_s"] = time.perf_counter() - t1
            control.append(line["control_widest_gap"])
        print(json.dumps(line), flush=True)
    summary = {"workload": args.workload, "seeds": len(seeds),
               "lower": max(program)}
    if control:
        summary["upper"] = min(control)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
