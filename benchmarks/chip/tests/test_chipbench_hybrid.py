"""The hybrid cell's pieces: ``flops_hybrid`` against hand counts for
K-EXAONE's cut, its three readers on a hand-built trace and tick log, the
cell's files, and a whole run of the harness on the CPU at a tiny size of the
same block with the plain reference ``references/exaone_moe.py``."""
import json
import sys
import time
from collections import deque
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import correct, flops, spec, trace as T  # noqa: E402
from chipbench import flops_hybrid as FH  # noqa: E402
from chipbench.cell import Run, run_cell, weight_seed  # noqa: E402
from repro.serving import telemetry  # noqa: E402

CELL = "k-exaone-236b-8l.chat"
NEW = ("hybrid_attn_roofline", "hybrid_serve_mfu", "expert_rows_per_step")
MS = 1_000_000                       # ns


def _model():
    return json.loads((HERE / "configs" / "k-exaone-236b-8l.json")
                      .read_text())["model"]


def _reader(name):
    return spec.load_module(HERE / "metrics" / f"{name}.py")


def test_hand_counts():
    m = _model()
    assert FH.layers(m) == [(False, 128), (True, 128), (True, 128),
                            (True, None)] + [(True, 128)] * 3 + [(True, None)]
    # q and o 6144 x 8192, k and v 6144 x 1024
    assert FH.attn_params(m) == 113_246_208
    assert FH.mlp_params(m) == 3 * 6144 * 18432 == 339_738_624
    # the shared expert, 8 x 8/128 = 0.5 routed experts, the router
    assert FH.expert_params(m) == 1.5 * 3 * 6144 * 2048 + 6144 * 128
    assert FH.matmul_params(m) == (153600 * 6144 + 8 * 113_246_208
                                   + 339_738_624 + 7 * 57_409_536)
    # at 1000 keys: 6 windowed layers read 128, the 2 full ones 1000
    per_key = 4 * 64 * 128
    assert FH.attn_flops(m, 1000) == per_key * (6 * 128 + 2 * 1000)
    assert FH.attn_flops(m, 100) == per_key * 8 * 100
    assert FH.decode_flops(m, 1000) == (2 * FH.matmul_params(m)
                                        + FH.attn_flops(m, 1000))
    # a 300-token prompt: windowed layers 1..128 keys, then 128 each
    win = 128 * 129 // 2 + 172 * 128
    assert FH.prefill_flops(m, 300) == (
        2 * (FH.matmul_params(m) - 153600 * 6144) * 300
        + per_key * (6 * win + 2 * 300 * 301 // 2) + 2 * 6144 * 153600)
    fl, by = FH.attn_work(m, [1000, 50])
    assert fl == FH.attn_flops(m, 1000) + FH.attn_flops(m, 50)
    kv, qo = 2 * 8 * 128 * 2, 2 * 64 * 128 * 2
    assert by == (6 * 128 + 2 * 1000 + 8 * 50) * kv + 2 * 8 * qo


def test_the_cell_and_its_files():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.data["pool_blocks"] == 4096
    # every accepted reader but the two that count dense GQA work
    # (chipbench.flops), and the new ones
    dense = {"paged_attn_roofline", "serve_mfu"}
    accepted = set(spec.readers(spec.load_cell("chatglm3-6b.chat")))
    assert set(spec.readers(cell)) == (accepted - dense) | set(NEW)
    ref = spec.reference(cell)
    assert callable(ref.logits) and callable(ref.gap_of)
    assert {m["name"] for m in cell.end_to_end} == {
        "output_tok_s", "ttft_p90_ms", "itl_p90_ms", "setup_s"}
    # the old cells do not read the new metrics
    for old in ("chatglm3-6b.chat", "chameleon-34b-6l.chat"):
        assert not set(NEW) & set(spec.readers(spec.load_cell(old)))


class _Rec:
    """A stream recorder's surface, as the readers use it."""

    def __init__(self, rows, steps, stamps=None, prompt_len=None):
        self._rows, self._steps = rows, steps
        self.stamps = stamps or {}
        self.stream = SimpleNamespace(prompt_len=prompt_len or {})
        self.seconds = 10.0

    def decode_rows(self, t0, t1):
        return self._rows

    def decode_steps(self, t0, t1):
        return self._steps

    def in_window(self, t):
        return 10.0 <= t < 20.0


def _trace(kernel_ms):
    tr = T.DeviceTrace()
    chip = "/device:TPU:0"
    names = ["custom-call(s32[48,214] %x)", "fusion.1",
             "custom-call(s32[48,214] %y)"]
    tr.ops[chip] = [(0, kernel_ms[0] * MS), (5 * MS, 6 * MS),
                    (6 * MS, 6 * MS + kernel_ms[1] * MS)]
    tr.op_names[chip] = names
    tr.modules[chip] = []
    tr.window_ns = (0, 10 * MS)
    return tr


def _run(rec, tr=None):
    return Run(cell=spec.load_cell(CELL), rec=rec,
               peak=flops.peaks("TPU v5 lite"), trace=tr, traced=(10.0, 20.0))


def test_hybrid_attn_roofline_reads_the_kernel_against_the_window_work():
    m = _model()
    rec = _Rec([(1, 1000), (2, 50)], 2)
    v = _reader("hybrid_attn_roofline").read(_run(rec, _trace((2, 3))))
    least = flops.roofline_s(*FH.attn_work(m, [1000, 50]),
                             flops.peaks("TPU v5 lite"))
    assert v == pytest.approx(100 * least / 5e-3)
    # no kernel in the trace: nothing
    tr = _trace((2, 3))
    tr.op_names["/device:TPU:0"] = ["fusion.0", "fusion.1", "fusion.2"]
    assert _reader("hybrid_attn_roofline").read(_run(rec, tr)) is None


def test_hybrid_serve_mfu_counts_the_useful_tokens():
    m = _model()
    # request 1: first token at 11 (its prompt in the window), then two
    # decode tokens at 12 and 25 (past the window); request 2: first token
    # before the window, one decode token in it
    rec = _Rec([], 0, stamps={1: [11.0, 12.0, 25.0], 2: [9.0, 15.0]},
               prompt_len={1: 300, 2: 40})
    v = _reader("hybrid_serve_mfu").read(_run(rec))
    total = (FH.prefill_flops(m, 300) + FH.decode_flops(m, 301)
             + FH.decode_flops(m, 41))
    assert v == pytest.approx(100 * total / (10.0 * 197e12))
    assert _reader("hybrid_serve_mfu").read(
        _run(_Rec([], 0, stamps={1: [1.0]}, prompt_len={1: 5}))) is None


@pytest.fixture
def log(monkeypatch):
    d = deque(maxlen=telemetry.MAX_TICKS)
    monkeypatch.setattr(telemetry, "LOG", d)
    return d


def test_expert_rows_per_step(log):
    tick = telemetry.Tick
    log.extend([tick(9.0, 0, 0, 0, 0, 0, 500),            # before the window
                tick(12.0, 1, 1, 10, 5, 5, 100),
                tick(15.0, 2, 0, 0, 5, 5, 12),
                tick(21.0, 3, 0, 0, 5, 5, 700)])          # after it
    v = _reader("expert_rows_per_step").read(_run(_Rec([], 2)))
    # 112 assignments over 8 held experts x 7 expert layers x 2 steps
    assert v == pytest.approx(112 / (8 * 7 * 2))


def test_expert_rows_per_step_reads_nothing_without_the_count(log):
    """A program whose tick log has no ``expert_rows``: nothing."""
    log.append(telemetry.Tick(15.0, 2, 0, 0, 5, 5))        # the default 0
    assert _reader("expert_rows_per_step").read(_run(_Rec([], 2))) is None
    log.clear()

    class Bare(tuple):                                    # no such field
        t = 15.0
    log.append(Bare())
    assert _reader("expert_rows_per_step").read(_run(_Rec([], 2))) is None


# a tiny model of the same block: two LLLG periods, a dense layer 0, window
# 8; 4 experts, all of them picked (top 4) so that no near tie in the
# routing changes which experts a token uses, 2 of them held
LLLG = ["sliding_attention"] * 3 + ["full_attention"]
TINY = {"name": "tiny-exaone", "family": "moe", "n_layers": 8, "d_model": 64,
        "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 96,
        "vocab": 512, "block_pattern": ["attn_moe"], "first_k_dense": 1,
        "rope_fraction": 1.0, "rope_theta": 10000.0, "window": 8,
        "layer_types": LLLG * 2, "full_rope": False, "qk_norm": True,
        "post_norm": True, "norm": "rmsnorm", "act": "swiglu",
        "norm_eps": 1e-5,
        "moe": {"n_experts": 4, "top_k": 4, "d_ff_expert": 32,
                "n_shared_experts": 1, "scoring": "sigmoid",
                "routed_scale": 2.5, "n_held": 2},
        "tie_embeddings": False, "dtype": "bfloat16",
        "param_dtype": "bfloat16"}
TRAFFIC = {"slots": 4, "block": 8, "chunk": 32,
           "prompt": {"kind": "uniform", "min": 8, "max": 64},
           "output": {"kind": "uniform", "min": 24, "max": 64},
           "n_lengths": 8, "resident_target": 3,
           "arrival_every_ticks": 15.0}
# sound runs read about 0.05 at this size and the float8 control 0.7-1.4
TINY_LIMIT = 0.2


def test_whole_run_at_a_tiny_size_is_correct_and_the_control_is_not():
    cell = spec.load_cell(CELL)
    cell.traffic = dict(TRAFFIC)
    cell.config = dict(cell.config, model=dict(TINY))
    cell.data = {"pool_blocks": 64, "widest_gap": TINY_LIMIT}
    seed = 2 ** 31 + 99
    res = run_cell(cell, seed, 1.0, False, t_start=time.perf_counter(),
                   log=lambda s: None)
    assert res.correct, res.compared
    assert res.failed == 0 and res.attempted > 0 and res.seqs
    ref = spec.reference(cell)
    control = correct.control_token_gaps(ref, TINY, weight_seed(seed),
                                         res.seqs)
    assert not correct.verdict(control, TINY_LIMIT)[0]
