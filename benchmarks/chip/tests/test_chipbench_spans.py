"""The readers of the program's spans and tick log, on a hand-built trace and
hand-made tick records."""
import sys
from collections import deque
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import spec, trace as T  # noqa: E402
from chipbench.cell import Run  # noqa: E402
from repro.serving import telemetry  # noqa: E402

NEW = ("host_ms_per_tick", "idle_behind_host_ms_per_tick",
       "kv_written_share", "prefill_chunk_fill")
MS = 1_000_000                       # ns


def _reader(name):
    return spec.load_module(HERE / "metrics" / f"{name}.py")


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell("chatglm3-6b.chat")


def _trace():
    """Two decode ticks of 10 ms on the device with the host between them.

    tick 0: admit [0,1) prefill [1,2) pick [2,3.5) emit [3.5,4)
            prepare [4,5) dispatch [5,6) sync [6,12) emit [12,14)
    tick 1: admit [14,15) prepare [15,17) dispatch [17,18) sync [18,30)
            emit [30,31), a collector pause [30.2, 30.6) inside the emit
    device: [1.5,3) prefill, [5.5,11) decode, [17.5,29) decode
    """
    tr = T.DeviceTrace()
    chip = "/device:TPU:0"
    dev = [(1.5, 3), (5.5, 11), (17.5, 29)]
    tr.ops[chip] = [(int(s * MS), int(e * MS)) for s, e in dev]
    tr.op_names[chip] = ["chunk", "decode", "decode"]
    tr.modules[chip] = []
    spans = [("serve.admit", 0, 1), ("serve.prefill", 1, 2),
             ("serve.pick", 2, 3.5), ("serve.emit", 3.5, 4),
             ("serve.prepare", 4, 5), ("serve.dispatch", 5, 6),
             ("serve.sync", 6, 12), ("serve.emit", 12, 14),
             ("serve.admit", 14, 15), ("serve.prepare", 15, 17),
             ("serve.dispatch", 17, 18), ("serve.sync", 18, 30),
             ("serve.emit", 30, 31), ("serve.gc", 30.2, 30.6),
             ("np.asarray(jax.Array)", 6.1, 11.9)]
    tr.host = [(n, int(s * MS), int(e * MS)) for n, s, e in spans]
    tr.window_ns = (0, 31 * MS)
    return tr


def _run(cell, tr=None, traced=(10.0, 20.0)):
    return Run(cell=cell, rec=None, peak={}, trace=tr or _trace(),
               traced=traced)


def test_new_readers_are_declared_for_both_cells(cell):
    assert set(NEW) <= set(spec.readers(cell))
    assert set(NEW) <= set(spec.readers(
        spec.load_cell("chameleon-34b-6l.chat")))


def test_host_ms_per_tick(cell):
    # spans other than the two waits (pick, sync):
    # 1+1+0.5+1+1+2 + 1+2+1+1 = 11.5 ms; the collector pause lies inside
    # the emit and counts once; two syncs
    v = _reader("host_ms_per_tick").read(_run(cell))
    assert v == pytest.approx(11.5 / 2)


def test_idle_behind_host_ms_per_tick(cell):
    # device idle: [0,1.5) [3,5.5) [11,17.5) [29,31)
    # behind the host's own spans: [0,1.5) 1.5, [3.5,5.5) 2, [12,17.5) 5.5,
    # [30,31) 1; the rest ([3,3.5) in the pick, [11,12) and [29,30) in the
    # syncs) is the host waiting on the device
    v = _reader("idle_behind_host_ms_per_tick").read(_run(cell))
    assert v == pytest.approx((1.5 + 2 + 5.5 + 1) / 2)
    # the breakdown names each gap by the phase the host was in most
    gaps = [[name, round(d * 1e3, 6)] for name, d in _trace().idle_gaps()]
    assert gaps == [["serve.emit", 6.5], ["serve.prepare", 2.5],
                    ["serve.sync", 2.0], ["serve.admit", 1.5]]


def test_span_readers_read_nothing_without_spans(cell):
    tr = _trace()
    tr.host = [h for h in tr.host if not h[0].startswith("serve.")]
    for name in ("host_ms_per_tick", "idle_behind_host_ms_per_tick"):
        assert _reader(name).read(_run(cell, tr)) is None, name


def _tick(t, tick, chunks=0, chunk_tokens=0, reserved=0, written=0):
    return telemetry.Tick(t, tick, chunks, chunk_tokens, reserved, written)


@pytest.fixture
def log(monkeypatch):
    d = deque(maxlen=telemetry.MAX_TICKS)
    monkeypatch.setattr(telemetry, "LOG", d)
    return d


def test_tick_log_readers(cell, log):
    chunk = cell.traffic["chunk"]
    log.extend([
        _tick(9.0, 0, chunks=5, chunk_tokens=1, reserved=1000, written=1),
        _tick(10.0, 1, chunks=2, chunk_tokens=chunk + 10,
              reserved=100, written=80),
        _tick(15.0, 2, chunks=1, chunk_tokens=chunk,
              reserved=100, written=90),
        _tick(19.5, 3, reserved=200, written=170),
        _tick(20.0, 4, chunks=9, reserved=9, written=0),    # past the window
    ])
    run = _run(cell)
    assert _reader("kv_written_share").read(run) == \
        pytest.approx(100 * (80 + 90 + 170) / (100 + 100 + 200))
    assert _reader("prefill_chunk_fill").read(run) == \
        pytest.approx(100 * (2 * chunk + 10) / (3 * chunk))


def test_tick_log_readers_read_nothing_without_records(cell, log):
    log.append(_tick(25.0, 0, chunks=1, chunk_tokens=3, reserved=5,
                     written=1))
    for name in ("kv_written_share", "prefill_chunk_fill"):
        assert _reader(name).read(_run(cell)) is None, name


def test_tick_log_readers_without_the_log_in_the_program(cell, log,
                                                        monkeypatch):
    """A program without the tick log: the readers return nothing."""
    import repro.serving
    log.append(_tick(15.0, 0, chunks=1, chunk_tokens=3, reserved=5,
                     written=1))
    monkeypatch.delattr(repro.serving, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.serving.telemetry", None)
    for name in ("kv_written_share", "prefill_chunk_fill"):
        assert _reader(name).read(_run(cell)) is None, name
