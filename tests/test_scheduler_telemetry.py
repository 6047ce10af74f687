"""The serving engine's telemetry: the ``serve.*`` phase spans in a profiler
trace, the process-wide tick log, the collector's spans, and due-time TTFT.

The paged ``Scheduler`` runs a width-64 model under ``jax.profiler.trace``;
the trace is read back with ``jax.profiler.ProfileData``."""
import gc
import glob
import os
import time
from collections import deque

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.config import ModelConfig, ParallelConfig
from repro.launch.scheduler import Request, Scheduler
from repro.models import transformer as T
from repro.serving import telemetry

PCFG = ParallelConfig(remat="none", fsdp_params=False)
TINY = dict(name="tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
            n_kv_heads=2, d_ff=128, vocab=128, rope_fraction=0.5,
            norm="rmsnorm", act="swiglu", dtype="float32",
            param_dtype="float32")
PHASES = ("serve.admit", "serve.prefill", "serve.pick", "serve.prepare",
          "serve.dispatch", "serve.sync", "serve.emit")
CHUNK = 16


class Stop(Exception):
    """Ends a run from its token callback, as a benchmark's window does."""


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(**TINY)
    return cfg, T.init(jax.random.PRNGKey(0), cfg)


def _sched(model, **kw):
    cfg, params = model
    kw = dict(dict(slots=2, max_len=64, paged=True, block=8, chunk=CHUNK,
                   pool_blocks=16), **kw)
    sched = Scheduler(cfg, PCFG, params, **kw)
    # compile both programs outside any trace
    sched.run([Request(rid=0, prompt=np.arange(CHUNK + 1) % cfg.vocab,
                       gen=2)])
    sched.reset()
    return sched


def _requests(vocab):
    """Three requests due at tick 0 on two slots (one waits for a slot),
    prompts of one, two and three chunks and an empty one; arrivals leave
    the engine no idle tick."""
    rng = np.random.RandomState(3)
    spec = [(40, 5, 0), (9, 6, 0), (20, 3, 0), (0, 4, 2), (17, 2, 6)]
    return [Request(rid=i, prompt=rng.randint(0, vocab, (lp,)).astype(np.int32),
                    gen=gen, arrival=arr)
            for i, (lp, gen, arr) in enumerate(spec)]


def _spans(trace_dir):
    """``(name, start_ns, end_ns, tick)`` of every ``serve.*`` host event."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    stats = dict(ev.stats)
                    out.append((ev.name, ev.start_ns, ev.end_ns,
                                stats.get("tick")))
    return sorted(out, key=lambda s: (s[1], s[2]))


def _traced(sched, reqs, trace_dir, on_token=None):
    t0 = time.perf_counter()
    with jax.profiler.trace(str(trace_dir)):
        try:
            out = sched.run(reqs, on_token=on_token)
        except Stop:
            out = None
    t1 = time.perf_counter()
    return out, telemetry.records(t0, t1), _spans(str(trace_dir))


@pytest.fixture(scope="module")
def served(model, tmp_path_factory):
    sched = _sched(model)
    reqs = _requests(model[0].vocab)
    out, recs, spans = _traced(sched, reqs, tmp_path_factory.mktemp("tr"))
    return reqs, out, recs, spans


def _decode_ticks(reqs, comps):
    """The ticks in which the decode step ran, from the completions alone: a
    request's decode tokens come one a tick, the last in the tick before
    ``done_tick`` (the engine counts that tick before its tokens stream)."""
    ticks = set()
    for q in reqs:
        c = comps[q.rid]
        n = q.gen - (1 if len(q.prompt) else 0)
        ticks.update(range(c.done_tick - n, c.done_tick))
    return sorted(ticks)


def test_one_dispatch_and_one_sync_per_decode_tick(served):
    reqs, out, _, spans = served
    decode_ticks = _decode_ticks(reqs, out["completions"])
    assert decode_ticks
    for name in ("serve.dispatch", "serve.sync", "serve.prepare"):
        assert [s[3] for s in spans if s[0] == name] == decode_ticks, name


def test_first_tokens_wait_once_a_tick_after_the_chunks(served):
    """The tick's chunk calls come first, then one ``serve.pick`` for the
    prompts they completed, then those first tokens' ``serve.emit``."""
    reqs, out, _, spans = served
    phases = [s for s in spans if s[0] in PHASES]
    picks = [s[3] for s in phases if s[0] == "serve.pick"]
    comps = out["completions"]
    assert picks == sorted({comps[q.rid].admitted_tick
                            + -(-len(q.prompt) // CHUNK) - 1
                            for q in reqs if len(q.prompt)})
    for i, s in enumerate(phases):
        if s[0] == "serve.pick":
            assert phases[i - 1][0] == "serve.prefill", phases[i - 1]
            assert phases[i + 1][0] == "serve.emit", phases[i + 1]
            assert phases[i - 1][3] == phases[i + 1][3] == s[3]


def test_phase_spans_tile_ticks_without_overlap(served):
    _, _, _, spans = served
    phases = [s for s in spans if s[0] in PHASES]
    assert {s[0] for s in phases} == set(PHASES)
    for a, b in zip(phases, phases[1:]):
        assert a[2] <= b[1], (a, b)
        assert a[3] <= b[3], (a, b)            # ticks never go back
    # every span carries its tick; none is a tick-wide or run-wide span
    assert all(s[3] is not None for s in phases)
    assert not {s[0] for s in spans} - set(PHASES) - {"serve.gc"}


def test_tick_log_matches_the_run(served):
    reqs, out, recs, _ = served
    assert [r.tick for r in recs] == list(range(out["ticks"]))
    assert sum(r.chunk_tokens for r in recs) == sum(len(q.prompt)
                                                    for q in reqs)
    assert sum(r.chunks for r in recs) == sum(-(-len(q.prompt) // CHUNK)
                                              for q in reqs)
    for r in recs:
        assert 0 <= r.written_pages <= r.reserved_pages <= 16, r
        assert all(isinstance(v, (int, float)) for v in r), r
    assert [r.t for r in recs] == sorted(r.t for r in recs)


def test_tick_log_stays_bounded(model, monkeypatch):
    monkeypatch.setattr(telemetry, "LOG", deque(maxlen=5))
    sched = _sched(model)
    out = sched.run(_requests(model[0].vocab))
    assert len(telemetry.LOG) == 5
    assert [r.tick for r in telemetry.LOG] == list(range(out["ticks"] - 5,
                                                         out["ticks"]))
    assert telemetry.MAX_TICKS == 65536


def test_ttft_counts_the_wait_for_a_slot(served):
    reqs, out, _, _ = served
    comps = out["completions"]
    waited = [c for c in comps.values() if c.admitted_tick > c.arrival]
    assert waited
    for c in comps.values():
        assert c.due_s <= c.admitted_s <= c.first_token_s
        assert c.ttft_s >= c.first_token_s - c.admitted_s
    for c in waited:
        assert c.ttft_s > c.first_token_s - c.admitted_s


@pytest.mark.parametrize("after", [1, 6])
def test_a_callback_that_raises_leaves_no_span_open(model, tmp_path, after):
    """The run ends from its callback in a first token's or a decode step's
    emit: that span is closed, a decode tick logged, the hook gone."""
    sched = _sched(model)
    hooks = list(gc.callbacks)
    seen = []

    def on_token(rid, tok):
        seen.append(rid)
        if len(seen) == after:
            raise Stop

    out, recs, spans = _traced(sched, _requests(model[0].vocab), tmp_path,
                               on_token)
    assert out is None and gc.callbacks == hooks
    *_, wait, last = [s for s in spans if s[0] in PHASES]
    assert last[0] == "serve.emit" and wait[3] == last[3]
    assert wait[0] == ("serve.pick" if after == 1 else "serve.sync")
    if after > 1:
        assert recs[-1].tick == last[3]
    # the next run on the same engine tiles its ticks again
    sched.reset()
    out2, _, spans2 = _traced(sched, _requests(model[0].vocab),
                              tmp_path / "again")
    phases = [s for s in spans2 if s[0] in PHASES]
    assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
    assert len(out2["completions"]) == 5


def test_collector_pauses_are_spans(model, tmp_path):
    sched = _sched(model)
    hooks = list(gc.callbacks)

    def on_token(rid, tok):
        if rid == 1:
            gc.collect()

    _, _, spans = _traced(sched, _requests(model[0].vocab), tmp_path,
                          on_token)
    gcs = [s for s in spans if s[0] == "serve.gc"]
    assert gcs and gc.callbacks == hooks
    # each pause lies inside the phase the host was in
    phases = [s for s in spans if s[0] in PHASES]
    for g in gcs:
        assert any(p[1] <= g[1] and g[2] <= p[2] for p in phases), g


def test_end_aligned_engine_is_spanned_and_logged(model, tmp_path):
    sched = _sched(model, paged=False, max_len=48)
    reqs = _requests(model[0].vocab)
    out, recs, spans = _traced(sched, reqs, tmp_path)
    names = {s[0] for s in spans}
    assert not names & {"serve.prefill", "serve.pick"}
    assert "serve.sync" in names
    assert [r.tick for r in recs] == list(range(out["ticks"]))
    assert all(r.chunks == r.reserved_pages == 0 for r in recs)
