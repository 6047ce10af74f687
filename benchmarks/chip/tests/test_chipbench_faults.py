"""A whole run of the harness on the CPU at a tiny size, past its look for a
chip: sound, it comes out correct; with the timed path broken underneath,
``correct`` comes out false, once for each fault a serving cell can have.
And the control, the reference at float8, fails the same comparison."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import correct, spec  # noqa: E402
from chipbench.cell import run_cell, weight_seed  # noqa: E402

SEED = 2 ** 31 + 99
# the limit at this size: a width-64 model's logits spread less than a
# full-width one's, so its gaps are smaller; sound runs read under 0.05 here
# and the float8 control about 0.45
TINY_LIMIT = 0.2
TRAFFIC = {"slots": 4, "block": 8, "chunk": 32,
           "prompt": {"kind": "uniform", "min": 8, "max": 64},
           "output": {"kind": "uniform", "min": 24, "max": 64},
           "n_lengths": 8, "resident_target": 3,
           "arrival_every_ticks": 15.0}
TINY = {"name": "tiny", "family": "dense", "n_layers": 2, "d_model": 64,
        "n_heads": 4, "n_kv_heads": 2, "d_ff": 128, "vocab": 512,
        "rope_fraction": 0.5, "rope_theta": 10000.0, "norm": "rmsnorm",
        "act": "swiglu", "norm_eps": 1e-5, "qk_norm": False,
        "tie_embeddings": False, "dtype": "bfloat16",
        "param_dtype": "bfloat16"}


def _cell():
    cell = spec.load_cell("chatglm3-6b.chat")
    cell.traffic = dict(TRAFFIC)
    cell.config = dict(cell.config, model=dict(TINY))
    cell.data = {"pool_blocks": TRAFFIC["slots"] * 16,
                 "widest_gap": TINY_LIMIT}
    return cell


def _decode_token_altered(steps, vocab):
    orig = steps.make_decode_step

    def make(*a, **kw):
        step = orig(*a, **kw)

        def altered(*args):
            tok, cache = step(*args)
            return (tok + 1) % vocab, cache
        return altered
    return "make_decode_step", make


def _prefill_token_altered(steps, vocab):
    orig = steps.make_chunk_prefill_step

    def make(*a, **kw):
        step = orig(*a, **kw)

        def altered(*args):
            logits, cache = step(*args)
            return -logits, cache          # the worst token comes first
        return altered
    return "make_chunk_prefill_step", make


def _state_unchanged(steps, vocab):
    orig = steps.make_decode_step

    def make(*a, **kw):
        step = orig(*a, **kw)

        def unchanged(params, tok, cache, *rest):
            nxt, _ = step(params, tok, cache, *rest)
            return nxt, cache              # K/V of the step never written
        return unchanged
    return "make_decode_step", make


FAULTS = {"decode_token_altered": _decode_token_altered,
          "prefill_token_altered": _prefill_token_altered,
          "decode_state_unchanged": _state_unchanged}


def _run(cell):
    return run_cell(cell, SEED, 1.0, False, t_start=time.perf_counter(),
                    log=lambda s: None)


@pytest.fixture(scope="module")
def sound():
    cell = _cell()
    return cell, _run(cell)


def test_sound_run_is_correct(sound):
    _, res = sound
    assert res.correct, res.compared
    assert res.failed == 0 and res.attempted > 0 and res.seqs
    assert set(res.metrics) == {"output_tok_s", "ttft_p90_ms", "itl_p90_ms",
                                "setup_s"}


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["half_context"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    from repro.kernels import paged_attention as pa
    from repro.parallel import steps
    if fault == "half_context":
        orig = pa.paged_attention
        monkeypatch.setattr(pa, "paged_attention",
                            lambda q, k, v, t, n: orig(q, k, v, t, n // 2 + 1))
    else:
        name, make = FAULTS[fault](steps, TINY["vocab"])
        monkeypatch.setattr(steps, name, make)
    res = _run(_cell())
    assert not res.correct, res.compared
    assert res.failed > 0


def test_verdict():
    gaps = [np.array([0.0, 0.2]), np.array([0.0, 0.0, 0.05])]
    assert correct.verdict(gaps, None) == (False, 2, 0.2)
    assert correct.verdict(gaps, 0.1) == (False, 1, 0.2)
    assert correct.verdict(gaps, 0.3) == (True, 0, 0.2)
    assert correct.verdict([], 0.3)[0] is False


def test_control_at_float8_fails_the_comparison(sound):
    cell, res = sound
    ref = spec.reference(cell)
    program = correct.token_gaps(ref, TINY, weight_seed(SEED), res.seqs)
    control = correct.control_token_gaps(ref, TINY, weight_seed(SEED),
                                         res.seqs)
    assert correct.verdict(program, TINY_LIMIT)[0]
    assert not correct.verdict(control, TINY_LIMIT)[0]
