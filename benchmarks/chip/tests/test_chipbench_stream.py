"""Window, tick count and the end-to-end reductions of the stream recorder,
driven by a scripted engine on a fake clock."""
import statistics
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.stream import Recorder, WindowClosed, percentile  # noqa: E402
from chipbench.traffic import Stream  # noqa: E402


def _stream(n=40, every=2, gen=6, first_tick=2):
    arrival = {0: 0}
    gens = {0: first_tick + 1}
    for i in range(1, n + 1):
        arrival[i] = first_tick + (i - 1) * every
        gens[i] = gen
    return Stream(requests=[], prompt_len={r: 8 for r in arrival}, gen=gens,
                  arrival=arrival, cohort=frozenset({0}),
                  first_tick=first_tick)


def _drive(stream, seconds, tick_s):
    """The engine's emission order: per tick, first tokens of the requests
    admitted (one-chunk prompts) half-way through, then one decode token per
    decoding request at the tick's end.  ``tick_s(t)`` is tick t's length."""
    now = [0.0]
    rec = Recorder(stream, seconds, clock=lambda: now[0])
    count = {r: 0 for r in stream.arrival}
    t, start = 0, 0.0
    with pytest.raises(WindowClosed):
        while True:
            dur = tick_s(t)
            new = [r for r, a in stream.arrival.items() if a == t]
            now[0] = start + dur / 2
            for r in new:
                count[r] = 1
                rec(r, 1)
            for r in sorted(count):
                if 0 < count[r] < stream.gen[r]:
                    now[0] = start + dur
                    count[r] += 1
                    rec(r, 1)
            start += dur
            t += 1
    return rec


def test_percentile_is_statistics_quantiles():
    v = [float(x) for x in range(1, 201)]
    assert percentile(v, 90) == statistics.quantiles(v, n=100)[89]
    assert percentile([3.0], 99) == 3.0


def test_steady_engine_reads_as_it_runs():
    rec = _drive(_stream(), 1.0, lambda t: 0.1)
    rec.check_ticks()
    assert rec.t_open == pytest.approx(0.2)       # end of tick 1
    assert rec.t_close == pytest.approx(1.2)
    e2e = rec.end_to_end()
    # every request is due at its tick's start, first token half a tick on
    assert all(x == pytest.approx(0.05) for x in rec.ttfts())
    assert e2e["ttft_p90_ms"] == pytest.approx(50.0)
    assert e2e["itl_p90_ms"] == pytest.approx(100.0)
    # tokens stamped in [0.2, 1.2): 3 resident from tick 6 on, one each tick
    assert e2e["output_tok_s"] == pytest.approx(rec.tokens_in_window() / 1.0)
    assert rec.attempted() == 5
    steps = rec.decode_steps(rec.t_open, rec.t_close)
    rows = rec.decode_rows(rec.t_open, rec.t_close)
    assert steps == 10 and 2 <= len(rows) / steps <= 3


def test_a_stall_moves_every_metric():
    base = _drive(_stream(), 1.0, lambda t: 0.1).end_to_end()
    stalled = _drive(_stream(), 1.0,
                     lambda t: 0.4 if t == 5 else 0.1).end_to_end()
    assert stalled["itl_p90_ms"] > 3 * base["itl_p90_ms"]
    assert stalled["output_tok_s"] < base["output_tok_s"]
    assert stalled["ttft_p90_ms"] > base["ttft_p90_ms"]


def test_a_request_still_waiting_counts_its_wait():
    rec = _drive(_stream(), 1.0, lambda t: 0.1)
    # pretend the last request due never streamed: its wait runs to the close
    due = [r for r in rec.stream.arrival
           if rec.due(r) is not None and rec.in_window(rec.due(r))]
    last = max(due, key=rec.due)
    del rec.stamps[last]
    waits = rec.ttfts()
    assert max(waits) == pytest.approx(rec.t_close - rec.due(last))


def test_a_lost_tick_is_refused():
    rec = _drive(_stream(), 1.0, lambda t: 0.1)
    rec.tick_of[3] = [4, 5, 7]                     # a decode tick skipped
    with pytest.raises(RuntimeError, match="tick count lost"):
        rec.check_ticks()
    rec = _drive(_stream(), 1.0, lambda t: 0.1)
    rec.tick_of[3] = [1] + rec.tick_of[3][1:]      # streamed before arrival
    with pytest.raises(RuntimeError, match="before its arrival"):
        rec.check_ticks()


def test_queue_wait_counts_ticks_before_admission():
    rec = _drive(_stream(), 1.0, lambda t: 0.1)
    assert set(rec.queue_waits(chunk=256)) == {0}


def test_a_reader_that_reads_nothing_is_named_with_what_ran():
    from chipbench.cell import Run, silent_reader
    rec = _drive(_stream(), 1.0, lambda t: 0.1)
    run = Run(cell=None, rec=rec, peak={}, traced=(rec.t_open, rec.t_close))
    msg = silent_reader("decode_step_ms", run)
    assert "WARNING" in msg and "decode_step_ms" in msg
    assert f"{rec.decode_steps(rec.t_open, rec.t_close)} decode steps" in msg
    assert rec.decode_steps(rec.t_open, rec.t_close) > 0
