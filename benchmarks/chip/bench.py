"""The on-chip serving benchmark: one run of one cell.

  python3 benchmarks/chip/bench.py --workload chatglm3-6b.chat --seed 7 \
      --seconds 50 --trace 0

Reads the cell from ``BENCHMARK.json``, serves its traffic through the
program's paged ``Scheduler`` on the chip for ``--seconds``, checks a sample
of the served tokens against the plain reference, and prints one JSON object
as the last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end metrics, or with ``--trace 1`` the per-layer
ones), ``device``, with ``--trace 1`` a ``breakdown`` of the trace, and last
``compared``: each number compared with its limit.  The same numbers close
standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    from chipbench import spec
    cell = spec.load_cell(args.workload)

    import jax
    from repro.launch.cache import use_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    use_compile_cache()

    from chipbench.cell import run_cell
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START,
                   log=lambda s: print(s, file=sys.stderr, flush=True))
    out = {"correct": res.correct, "attempted": res.attempted,
           "failed": res.failed, "metrics": res.metrics, "device": res.device}
    if res.breakdown is not None:
        out["breakdown"] = res.breakdown
    out["compared"] = res.compared
    for name, (value, limit) in res.compared.items():
        print(f"{name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
