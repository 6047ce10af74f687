"""The trace reduction on synthetic device and host events."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import trace as T  # noqa: E402


def _trace():
    tr = T.DeviceTrace()
    chip = "/device:TPU:0"
    # ops: [0,10) [5,20) [30,40) [100,110) -> busy 20 + 10 + 10 = 40 ns
    tr.ops[chip] = [(0, 10), (5, 20), (30, 40), (100, 110)]
    tr.op_names[chip] = ["fusion.1", "paged_attn_kernel", "fusion.1",
                         "paged_attn_kernel"]
    tr.modules[chip] = [("jit_decode_paged(12)", 0, 40),
                        ("jit_chunk_prefill(3)", 100, 110),
                        ("jit_decode_paged(12)", 50, 60)]
    tr.host = [("PjitFunction(decode_paged)", 40, 70),
               ("np.asarray", 60, 100)]
    tr.window_ns = (0, 120)
    return tr


def test_union_and_gaps():
    assert T.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert T.union_ns([(0, 10), (2, 3), (10, 12)]) == 12
    assert T.gaps([(0, 10), (5, 20), (30, 40)], 0, 50) == [(20, 30), (40, 50)]
    assert T.gaps([], 3, 7) == [(3, 7)]


def test_busy_idle_and_window():
    tr = _trace()
    assert tr.busy_s() == 40e-9
    assert tr.window_s() == 120e-9
    # idle share the reader computes: 1 - 40/120
    assert abs((1 - tr.busy_s() / tr.window_s()) - 2 / 3) < 1e-12


def test_busy_averages_over_chips():
    tr = _trace()
    tr.ops["/device:TPU:1"] = [(0, 20)]
    tr.op_names["/device:TPU:1"] = ["x"]
    assert abs(tr.busy_s() - 30e-9) < 1e-18


def test_modules_and_kernel_time():
    tr = _trace()
    assert tr.module_times("jit_decode_paged") == [40e-9, 10e-9]
    assert tr.module_times("jit_chunk_prefill") == [10e-9]
    assert tr.module_times("jit_other") == []
    s, n = tr.op_time(r"paged_attn")
    assert (s, n) == (25e-9, 2)
    assert tr.op_time(r"nothing")[1] == 0


def test_top_ops_and_idle_gaps_named_by_host():
    tr = _trace()
    assert tr.top_ops(1) == [["paged_attn_kernel", 25e-9]]
    g = tr.idle_gaps()
    # gaps: [40,100) 60 ns, [20,30) 10 ns, [110,120) 10 ns
    assert g[0] == ["np.asarray", 60e-9]      # overlaps 40 ns of 60
    assert len(g) == 3 and g[1][0] == "host idle"
    assert T.module_base("jit_decode_paged(123)") == "jit_decode_paged"
