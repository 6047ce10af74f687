"""Compiled-HLO analysis: collective traffic + roofline terms.

``compiled.cost_analysis()`` reports **per-device** FLOPs/bytes for the SPMD
partitioned module (verified empirically); we multiply by chip count when
reporting "global HLO_FLOPs" so the spec formula
``compute = HLO_FLOPs / (chips × peak)`` applies literally.

collective_bytes is parsed from ``compiled.as_text()`` (post-partitioning, so
shapes are per-device).  Each op contributes its modeled per-device *wire*
traffic on a ring/torus:

  all-reduce        2·m·(p−1)/p     (reduce-scatter + all-gather)
  all-gather        m_out·(p−1)/p
  reduce-scatter    m_out·(p−1)
  all-to-all        m·(p−1)/p
  collective-permute m

(m = per-device result bytes, p = replica-group size).  The raw Σ result
bytes is also recorded.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_ARRAY_RE = re.compile(r"(pred|bf16|f16|f32|f64|f8e4m3fn|f8e5m2|[su]\d+|c\d+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[\d+\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_SRC_TGT_RE = re.compile(r"source_target_pairs=\{")


def _result_bytes(line: str, op_start: int) -> int:
    """Sum bytes of array literals in the result type: the segment between
    '=' and the op name (handles tuple results of async collectives)."""
    eq = line.find("=")
    if eq < 0:
        return 0
    seg = line[eq + 1: op_start]
    total = 0
    for dt, dims in _ARRAY_RE.findall(seg):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def _group_size(line: str, n_devices: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return n_devices


def collective_stats(hlo_text: str, n_devices: int) -> Dict:
    """Per-op-kind counts, raw result bytes, and modeled wire bytes."""
    stats = {k: {"count": 0, "result_bytes": 0, "wire_bytes": 0.0}
             for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if "-done(" in line:
            continue  # async pair: count the -start only
        kind = m.group(1)
        mb = _result_bytes(line, m.start(1))
        p = _group_size(line, n_devices)
        if kind == "all-reduce":
            wire = 2.0 * mb * (p - 1) / p
        elif kind == "all-gather":
            wire = mb * (p - 1) / p
        elif kind == "reduce-scatter":
            wire = mb * (p - 1)
        elif kind == "all-to-all":
            wire = mb * (p - 1) / p
        else:  # collective-permute
            wire = float(mb)
        s = stats[kind]
        s["count"] += 1
        s["result_bytes"] += mb
        s["wire_bytes"] += wire
    total_wire = sum(s["wire_bytes"] for s in stats.values())
    total_raw = sum(s["result_bytes"] for s in stats.values())
    return {"per_op": stats, "wire_bytes": total_wire, "result_bytes": total_raw}


def analyze_compiled(compiled, n_devices: int) -> Dict:
    """All dry-run artifacts for one cell: memory, flops, collectives."""
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    coll = collective_stats(txt, n_devices)
    flops_dev = float(ca.get("flops", 0.0))
    bytes_dev = float(ca.get("bytes accessed", 0.0))
    return {
        "chips": n_devices,
        "flops_per_device": flops_dev,
        "hlo_flops_global": flops_dev * n_devices,
        "bytes_per_device": bytes_dev,
        "collectives": coll,
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": ma.argument_size_in_bytes
            + ma.output_size_in_bytes + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        },
    }
