"""The work that serving a dense GQA decoder needs, counted from its shapes:
operations and bytes of the algorithm, whatever implements it, so a
rewritten kernel or step is read against the same work.  And the chip's
published peaks, keyed by ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"
BF16 = 2


def peaks(device_kind: str) -> dict:
    """The peaks of one chip; a device not in the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def hd(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies in one layer: q, k, v, o and the MLP."""
    d, h = m["d_model"], hd(m)
    attn = d * m["n_heads"] * h * 2 + 2 * d * m["n_kv_heads"] * h
    mlp = (3 if m.get("act", "swiglu") == "swiglu" else 2) * d * m["d_ff"]
    return attn + mlp


def param_count(m: dict) -> int:
    """Every parameter: embedding, untied head, layers and their norms."""
    d = m["d_model"]
    norms = 2 * d + (2 * hd(m) if m.get("qk_norm") else 0)
    head = 0 if m.get("tie_embeddings") else m["vocab"] * d
    return (m["vocab"] * d + head + d
            + m["n_layers"] * (layer_matmul_params(m) + norms))


def kv_bytes_per_token(m: dict) -> int:
    """K and V of one token over every layer, in bf16."""
    return m["n_layers"] * 2 * m["n_kv_heads"] * hd(m) * BF16


def attn_flops(m: dict, keys: int) -> int:
    """One query token against ``keys`` keys in every layer: QK^T and PV."""
    return m["n_layers"] * 4 * m["n_heads"] * hd(m) * keys


def prefill_flops(m: dict, prompt: int) -> int:
    """A prompt of ``prompt`` tokens, causal, with logits for its last token
    only (they give the first output token)."""
    mm = 2 * m["n_layers"] * layer_matmul_params(m) * prompt
    attn = attn_flops(m, prompt * (prompt + 1) // 2)
    return mm + attn + 2 * m["d_model"] * m["vocab"]


def decode_flops(m: dict, keys: int) -> int:
    """One decode token attending ``keys`` keys, with its logits."""
    return (2 * m["n_layers"] * layer_matmul_params(m) + attn_flops(m, keys)
            + 2 * m["d_model"] * m["vocab"])


def paged_attn_work(m: dict, contexts: Iterable[int]):
    """``(flops, bytes)`` the paged decode attention needs over all layers for
    decode rows attending ``contexts`` keys each: the live K/V pages, the
    query and the output; parked rows and dead pages are not work."""
    h = hd(m)
    qo = 2 * m["n_layers"] * m["n_heads"] * h * BF16
    kv = kv_bytes_per_token(m)
    flops = nbytes = 0
    for c in contexts:
        flops += attn_flops(m, c)
        nbytes += c * kv + qo
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time: the larger of the compute and the memory bound."""
    return max(flops / peak["bf16_flop_s"], nbytes / peak["hbm_bytes_s"])
