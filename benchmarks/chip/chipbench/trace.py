"""Reduction of a profiler trace to device time: busy union, executions of
each compiled program, kernel time, the top device operations and the
longest idle gaps, each gap named by what the host was doing in it.

Only the trace's own names are used.  The program has no named scopes yet,
so the decode and prefill programs are found by their XLA module names
(``jit_decode_paged``, ``jit_chunk_prefill``) and the paged-attention kernel
by its operation name.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

Interval = Tuple[int, int]          # (start_ns, end_ns)

_SUFFIX = re.compile(r"\(\d+\)$")


def module_base(name: str) -> str:
    """``jit_decode_paged(123)`` -> ``jit_decode_paged``."""
    return _SUFFIX.sub("", name).strip()


def union_ns(intervals: List[Interval]) -> int:
    """Total length covered by the union of ``intervals``."""
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    """The stretches of ``[lo, hi)`` that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


@dataclass
class DeviceTrace:
    """Device and host events of one traced window, in nanoseconds."""
    ops: Dict[str, List[Interval]] = field(default_factory=dict)       # per chip
    op_names: Dict[str, List[str]] = field(default_factory=dict)
    modules: Dict[str, List[Tuple[str, int, int]]] = field(default_factory=dict)
    host: List[Tuple[str, int, int]] = field(default_factory=list)
    window_ns: Tuple[int, int] = (0, 0)

    @property
    def chips(self) -> List[str]:
        return sorted(self.ops)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(union_ns(v) for v in self.ops.values()) / len(self.ops) / 1e9

    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    def module_times(self, base: str) -> List[float]:
        """Durations (s) of every execution of the program ``base`` on the
        first chip."""
        chip = self.chips[0]
        return [(e - s) / 1e9 for name, s, e in self.modules.get(chip, [])
                if module_base(name) == base]

    def op_time(self, pattern: str) -> Tuple[float, int]:
        """Summed device seconds and count of the operations whose name
        matches ``pattern``, on the first chip."""
        rx = re.compile(pattern)
        chip = self.chips[0]
        tot, n = 0, 0
        for name, (s, e) in zip(self.op_names[chip], self.ops[chip]):
            if rx.search(name):
                tot += e - s
                n += 1
        return tot / 1e9, n

    def top_ops(self, k: int = 10) -> List[list]:
        chip = self.chips[0]
        acc: Dict[str, int] = defaultdict(int)
        for name, (s, e) in zip(self.op_names[chip], self.ops[chip]):
            acc[name] += e - s
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The ``k`` longest stretches with no device operation, each named
        by the host event that overlaps it most (``host idle`` if none)."""
        chip = self.chips[0]
        lo, hi = self.window_ns
        found = sorted(gaps(self.ops[chip], lo, hi),
                       key=lambda g: g[0] - g[1])[:k]
        out = []
        for s, e in found:
            best, cover = "host idle", 0
            for name, hs, he in self.host:
                c = min(e, he) - max(s, hs)
                if c > cover:
                    best, cover = name, c
            out.append([best, (e - s) / 1e9])
        return out


def load(trace_dir: str) -> DeviceTrace:
    """Read the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{len(files)}")
    data = ProfileData.from_file(files[0])
    tr = DeviceTrace()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops, names, mods = [], [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        ops.append((ev.start_ns, ev.end_ns))
                        names.append(ev.name)
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        mods.append((ev.name, ev.start_ns, ev.end_ns))
            tr.ops[plane.name], tr.op_names[plane.name] = ops, names
            tr.modules[plane.name] = mods
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    tr.host.append((ev.name, ev.start_ns, ev.end_ns))
    if not tr.ops or not any(tr.ops.values()):
        raise RuntimeError("the trace holds no device operation")
    # the traced window: from the first to the last event of any plane
    starts = [s for v in tr.ops.values() for s, _ in v] + \
        [s for _, s, _ in tr.host]
    ends = [e for v in tr.ops.values() for _, e in v] + \
        [e for _, _, e in tr.host]
    tr.window_ns = (min(starts), max(ends))
    return tr
