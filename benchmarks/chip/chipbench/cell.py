"""One run of one cell: set-up, the measured window, the check.

Set-up draws the weights on the device from the seed through the serving
entry point, builds the paged ``Scheduler`` with the traffic's engine
settings, warms up the cell's two programs (the ``(slots,)`` decode step and
the ``(1, chunk)`` prefill step) and runs the pre-roll: the traffic's pacer
and cohort, until the steady arrivals begin.  The window then measures
``seconds`` of the same ``Scheduler.run``, which the recorder ends.  After
it: peak memory, the program's state freed, and the plain reference over a
sample of the finished requests.
"""
from __future__ import annotations

import gc
import math
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from . import correct, spec, traffic as traffic_mod
from .stream import Recorder, WindowClosed

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")
# the steady arrivals submitted cover twice the ticks of a window at 2 ms a
# tick: more than any run reaches
TICKS_PER_S = 500


@dataclass
class Run:
    """What a per-layer reader may read."""
    cell: spec.Cell
    rec: Recorder
    peak: dict
    trace: object = None                 # chipbench.trace.DeviceTrace
    traced: tuple = (0.0, 0.0)           # host clock of the traced window

    @property
    def model(self) -> dict:
        return self.cell.model


@dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, dict]
    device: dict
    compared: Dict[str, list]
    breakdown: Optional[dict] = None
    seqs: list = field(default_factory=list)    # (prompt, served) compared
    gaps: list = field(default_factory=list)    # widest gap of each


def weight_seed(seed: int) -> int:
    """The program's weight seed: 32 bits of ``seed``."""
    return seed % 2 ** 32


def silent_reader(name: str, run: Run) -> str:
    """The warning for a per-layer reader that read nothing: what the token
    stream shows ran in the traced window, which the reader should have
    found (a renamed program or kernel hides it from a reader that finds it
    by name)."""
    t0, t1 = run.traced
    steps = run.rec.decode_steps(t0, t1)
    firsts = sum(1 for st in run.rec.stamps.values() if t0 <= st[0] < t1)
    return (f"[cell] WARNING: per-layer metric {name} read nothing, and is "
            f"left out, while the traced window streamed {steps} decode "
            f"steps and {firsts} first tokens: its reader may no longer find "
            f"the program's names")


def _free(tree) -> None:
    import jax
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "delete"):
            leaf.delete()


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, log: Callable[[str], None] = print) -> Result:
    import jax
    import jax.monitoring

    from repro.config import ModelConfig
    from repro.launch.scheduler import Request, Scheduler
    from repro.launch.serve import serving_params
    from repro.parallel import planner

    from . import flops

    model = cell.model
    tr = cell.traffic
    dev = jax.devices()[0]
    peak = flops.peaks(dev.device_kind) if dev.platform == "tpu" else {}

    compiles: List[float] = []

    def on_compile(event, duration, **_):
        if event in COMPILE_EVENTS:
            compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    # -- set-up: weights, engine, warm-up --------------------------------
    t0 = time.perf_counter()
    mcfg = ModelConfig(**model)
    mcfg, params = serving_params(mcfg, weight_seed(seed))
    jax.block_until_ready(params)
    t_init = time.perf_counter()
    max_len = traffic_mod.max_len(tr)
    plan = planner.ParallelPlan(mesh_shape=(1, 1), fsdp_axes=(), tp=1,
                                grad="none", remat="none")
    sched = Scheduler(mcfg, plan, params, slots=tr["slots"], max_len=max_len,
                      paged=True, block=tr["block"], chunk=tr["chunk"],
                      pool_blocks=cell.data["pool_blocks"])
    # the prefill program (two chunks where the traffic has longer prompts)
    # and the decode program, with the final chunk's token pick
    warm = np.arange(min(tr["chunk"] + 1, max_len - 3),
                     dtype=np.int32) % mcfg.vocab
    sched.run([Request(rid=0, prompt=warm, gen=3)])
    jax.block_until_ready(sched.cache)
    sched.cache = None                 # one arena at a time
    gc.collect()
    sched.reset()
    jax.block_until_ready(sched.cache)
    t_warm = time.perf_counter()

    n_steady = int(TICKS_PER_S * 2 * seconds / tr["arrival_every_ticks"]) + 1
    stream = traffic_mod.make_stream(tr, seed, mcfg.vocab, n_steady, Request)
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    traced = [0.0, 0.0]

    def on_open():
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            traced[0] = time.perf_counter()

    rec = Recorder(stream, seconds, on_open=on_open)
    # what set-up made (the stream above all) is kept out of the collector's
    # passes in the window
    gc.collect()
    gc.freeze()
    try:
        sched.run(stream.requests, on_token=rec)
    except WindowClosed:
        pass
    else:
        raise RuntimeError("the request stream ran dry before the window "
                           "closed")
    finally:
        if trace_dir and traced[0]:
            traced[1] = time.perf_counter()
            jax.profiler.stop_trace()
    t_end = time.perf_counter()
    gc.unfreeze()
    rec.check_ticks()
    in_window = [t for t in compiles if rec.t_open <= t <= t_end]
    if in_window:
        raise RuntimeError(f"{len(in_window)} compilations inside the "
                           f"measured window")
    stats = dev.memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    pool = sched.pool.report()
    log(f"[cell] {cell.name} seed {seed}: init {t_init - t0:.3f} s, engine + "
        f"warm-up {t_warm - t_init:.3f} s, pre-roll {rec.t_open - t_warm:.3f} s"
        f" ({len(stream.cohort) - 1} cohort requests), window "
        f"{rec.t_close - rec.t_open:.3f} s, {len(rec.tick_end)} ticks, "
        f"peak {mem_peak} bytes")
    log(f"[cell] page pool at the close: {pool['reserved_blocks']} of "
        f"{pool['n_blocks']} pages reserved, {pool['live_blocks']} written, "
        f"{pool['live_requests']} requests; written peak since set-up "
        f"{pool['peak_occupancy']:.4f} of the pool")

    e2e = rec.end_to_end()
    e2e["setup_s"] = rec.t_open - t_start
    metrics = {}
    for m in cell.end_to_end:
        v = e2e[m["name"]]
        if isinstance(v, float) and math.isnan(v):
            raise RuntimeError(f"{m['name']}: nothing to measure in the window")
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # -- the program's state goes before the reference runs ---------------
    sched.cache = None
    _free(params)
    del sched, params
    gc.collect()

    prompts = {r.rid: np.asarray(r.prompt, np.int32) for r in stream.requests}
    served = {rid: len(t) for rid, t in rec.tokens.items()}
    pick = correct.sample(rec.finished(), stream.prompt_len, served, seed)
    seqs = [(prompts[r], np.asarray(rec.tokens[r], np.int32)) for r in pick]
    t_ref = time.perf_counter()
    gaps = correct.token_gaps(spec.reference(cell), model, weight_seed(seed),
                              seqs)
    limit = cell.data.get("widest_gap")
    ok, bad, widest = correct.verdict(gaps, limit)
    log(f"[cell] reference over {len(pick)} requests, "
        f"{sum(len(s) for _, s in seqs)} served tokens, "
        f"{sum(len(p) + len(s) for p, s in seqs)} positions: "
        f"{time.perf_counter() - t_ref:.3f} s; widest gap of each "
        f"{[float(g.max()) for g in gaps]}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": mem_peak}
    result = Result(correct=ok, attempted=rec.attempted(), failed=bad,
                    metrics=metrics, device=device,
                    compared={"widest_gap": [widest, limit]}, seqs=seqs,
                    gaps=[float(g.max()) for g in gaps])
    if trace_dir:
        from . import trace as trace_mod
        dtr = trace_mod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        run = Run(cell=cell, rec=rec, peak=peak, trace=dtr,
                  traced=tuple(traced))
        per_layer = {}
        for name, reader in spec.readers(cell).items():
            v = reader.read(run)
            if v is None:
                log(silent_reader(name, run))
                continue
            unit = next(m["unit"] for m in cell.per_layer
                        if m["name"] == name)
            per_layer[name] = {"value": v, "unit": unit}
        result.metrics = per_layer
        device["busy_s"] = dtr.busy_s()
        device["window_s"] = dtr.window_s()
        result.breakdown = {"device_ops": dtr.top_ops(),
                            "idle_gaps": dtr.idle_gaps()}
    return result
