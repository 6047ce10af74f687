"""Operation and byte counts against hand counts for both configurations,
and the peak table."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from chipbench import flops as F  # noqa: E402


def _model(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())["model"]


def test_chatglm3_hand_counts():
    m = _model("chatglm3-6b")
    # q 4096x4096, k and v 4096x256, o 4096x4096, gate/up/down 4096x13696
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 13696
    assert F.layer_matmul_params(m) == per_layer == 203_948_032
    assert F.param_count(m) == 6_243_454_976          # 12.49 GB in bf16
    assert F.kv_bytes_per_token(m) == 28 * 2 * 2 * 128 * 2 == 28_672
    # one decode token at 1000 keys: 2 x (28 layers + head) + attention
    assert F.decode_flops(m, 1000) == (2 * 28 * per_layer + 2 * 4096 * 65024
                                       + 28 * 4 * 32 * 128 * 1000)


def test_chameleon_hand_counts():
    m = _model("chameleon-34b-6l")
    per_layer = 2 * 8192 * 8192 + 2 * 8192 * 1024 + 3 * 8192 * 22016
    assert F.layer_matmul_params(m) == per_layer == 692_060_160
    # 6 layers + QK-norm scales + embedding and untied head of 65536 x 8192
    assert F.param_count(m) == 5_226_210_816          # 10.45 GB in bf16
    assert F.kv_bytes_per_token(m) == 6 * 2 * 8 * 128 * 2 == 24_576


def test_prefill_counts_causal_attention_once():
    m = _model("chatglm3-6b")
    p = 3
    keys = 1 + 2 + 3                                  # causal: 1, 2, 3 keys
    assert F.prefill_flops(m, p) == (2 * 28 * F.layer_matmul_params(m) * p
                                     + 28 * 4 * 32 * 128 * keys
                                     + 2 * 4096 * 65024)


def test_paged_attention_work_and_roofline():
    m = _model("chatglm3-6b")
    fl, by = F.paged_attn_work(m, [100, 300])
    assert fl == 28 * 4 * 32 * 128 * 400
    assert by == 400 * 28_672 + 2 * (2 * 28 * 32 * 128 * 2)
    peak = F.peaks("TPU v5 lite")
    assert peak["bf16_flop_s"] == 197e12 and peak["hbm_bytes_s"] == 819e9
    # decode attention is bound by bytes: 4 flops per 2 bytes per key-dim
    assert F.roofline_s(fl, by, peak) == by / 819e9


def test_unknown_device_is_refused():
    with pytest.raises(KeyError, match="no peaks"):
        F.peaks("TPU v99")
