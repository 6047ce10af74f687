"""The work that serving a hybrid decoder needs, counted from its shapes: a
stack whose layers differ in their attention (full, or a sliding window) and
in their MLP (dense, or an expert layer of which this chip holds a share), as
the configuration's ``model`` states them (``first_k_dense``,
``block_pattern``, ``layer_types``, ``window``, ``moe``).  Operations and
bytes of the algorithm, whatever implements it: a windowed layer reads at
most ``window`` keys, and an expert layer computes, per token, its shared
experts, its router and the routed experts that land on the experts held
here (``top_k`` x held / ``n_experts`` of them on average)."""
from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

from .flops import BF16, hd


def layers(m: dict) -> List[Tuple[bool, Optional[int]]]:
    """Per layer: (expert layer?, window or None)."""
    pattern = m.get("block_pattern", ["attn"])
    k = m.get("first_k_dense", 0)
    types = m.get("layer_types")
    out = []
    for i in range(m["n_layers"]):
        moe = i >= k and pattern[(i - k) % len(pattern)] == "attn_moe"
        full = types is not None and types[i] == "full_attention"
        out.append((moe, None if full else m.get("window")))
    return out


def keys_read(window: Optional[int], context: int) -> int:
    """Keys one query at ``context`` keys reads in a layer."""
    return context if window is None else min(context, window)


def attn_params(m: dict) -> int:
    d, h = m["d_model"], hd(m)
    return d * m["n_heads"] * h * 2 + 2 * d * m["n_kv_heads"] * h


def mlp_params(m: dict) -> int:
    """Dense MLP weights one token multiplies."""
    return 3 * m["d_model"] * m["d_ff"]


def expert_params(m: dict) -> float:
    """Expert-layer weights one token multiplies on this chip, on average:
    the shared experts, the router, and ``top_k`` x held / ``n_experts``
    routed experts."""
    e, d = m["moe"], m["d_model"]
    one = 3 * d * e["d_ff_expert"]
    held = e.get("n_held") or e["n_experts"]
    routed = e["top_k"] * held / e["n_experts"]
    return (e.get("n_shared_experts", 0) + routed) * one + d * e["n_experts"]


def matmul_params(m: dict) -> float:
    """Weights one token multiplies over every layer, and the head."""
    total = m["d_model"] * m["vocab"]
    for moe, _ in layers(m):
        total += attn_params(m) + (expert_params(m) if moe else mlp_params(m))
    return total


def attn_flops(m: dict, context: int) -> int:
    """One query token at ``context`` keys: QK^T and PV over every layer."""
    per_key = 4 * m["n_heads"] * hd(m)
    return sum(per_key * keys_read(w, context) for _, w in layers(m))


def decode_flops(m: dict, context: int) -> float:
    """One decode token attending ``context`` keys, with its logits."""
    return 2 * matmul_params(m) + attn_flops(m, context)


def prefill_flops(m: dict, prompt: int) -> float:
    """A prompt of ``prompt`` tokens, causal (and windowed where the layer
    is), with logits for its last token only."""
    body = 2 * (matmul_params(m) - m["d_model"] * m["vocab"]) * prompt
    per_key = 4 * m["n_heads"] * hd(m)
    attn = 0
    for _, w in layers(m):
        if w is None or w >= prompt:
            attn += per_key * prompt * (prompt + 1) // 2
        else:               # keys 1..w, then w for each later query
            attn += per_key * (w * (w + 1) // 2 + (prompt - w) * w)
    return body + attn + 2 * m["d_model"] * m["vocab"]


def attn_work(m: dict, contexts: Iterable[int]):
    """``(flops, bytes)`` the paged decode attention needs over all layers
    for decode rows at ``contexts`` keys each: the K/V each layer reads (its
    window's keys at most), the query and the output."""
    h = hd(m)
    kv = 2 * m["n_kv_heads"] * h * BF16               # one key's K and V
    qo = 2 * m["n_heads"] * h * BF16
    flops = nbytes = 0
    for c in contexts:
        flops += attn_flops(m, c)
        for _, w in layers(m):
            nbytes += keys_read(w, c) * kv + qo
    return flops, nbytes
