"""The stream recorder: stamps every token the engine streams, finds the
engine's ticks in that stream, opens and closes the measured window, and
reduces the stamps to the end-to-end metrics.

``Scheduler.run(on_token=...)`` calls back once per token after the tick's
host sync.  Within one tick the first tokens of requests whose prefill
finished come first, then the decode step's tokens, one per decoding
request.  So a decode step's tokens start a new tick when a request repeats
within the current step, or when they follow a first token.  Ticks in which
nothing decodes stream nothing and cannot be counted: the traffic's pacer
covers the pre-roll, and ``check_ticks`` refuses a run in which the count
went wrong.

A request is due when the engine reaches its arrival tick: the time of the
last token of the previous tick's decode step.
"""
from __future__ import annotations

import math
import time
from statistics import quantiles
from typing import Dict, List, Optional


class WindowClosed(Exception):
    """Raised from ``on_token`` to end ``Scheduler.run`` at the window's end."""


def percentile(values: List[float], pct: int) -> float:
    """The ``pct``-th percentile, Python's ``statistics.quantiles`` with 100
    cuts (exclusive method), over every value given."""
    if not values:
        raise ValueError("percentile of no values")
    if len(values) == 1:
        return float(values[0])
    return float(quantiles(values, n=100)[pct - 1])


class Recorder:
    """``on_token`` target for one run; all times from ``time.perf_counter``."""

    def __init__(self, stream, seconds: float, on_open=None, clock=None):
        self.stream = stream
        self.seconds = seconds
        self.on_open = on_open            # called once when the window opens
        self.clock = clock or time.perf_counter
        self.stamps: Dict[int, List[float]] = {}
        self.tokens: Dict[int, List[int]] = {}
        self.tick_of: Dict[int, List[int]] = {}   # decode tick of each token
        self.tick_end: List[float] = []   # stamp of each tick's last token
        self._step: set = set()           # rids of the current decode step
        self._prev_first = True
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None

    # -- the callback ----------------------------------------------------
    def __call__(self, rid: int, tok: int) -> None:
        now = self.clock()
        seen = self.stamps.setdefault(rid, [])
        ticks = self.tick_of.setdefault(rid, [])
        if not seen:                       # a first token, from prefill
            self._prev_first = True
            ticks.append(len(self.tick_end))
        else:                              # a decode step's token
            if self._prev_first or rid in self._step:
                self.tick_end.append(now)
                self._step = set()
            else:
                self.tick_end[-1] = now
            self._step.add(rid)
            self._prev_first = False
            ticks.append(len(self.tick_end) - 1)
        seen.append(now)
        self.tokens.setdefault(rid, []).append(int(tok))
        if self.t_open is None and ticks[-1] >= self.stream.first_tick:
            # the engine is in tick first_tick: the window opened at the end
            # of the previous tick
            self.t_open = self.tick_end[self.stream.first_tick - 1]
            if self.on_open is not None:
                self.on_open()
        if self.t_open is not None and now >= self.t_open + self.seconds:
            self.t_close = self.t_open + self.seconds
            raise WindowClosed

    # -- reductions --------------------------------------------------------
    def due(self, rid: int) -> Optional[float]:
        """Wall time at which the engine reached the request's arrival tick
        (None if it never did)."""
        a = self.stream.arrival[rid]
        if a == 0:
            return None                    # pre-roll: due before set-up ended
        return self.tick_end[a - 1] if a - 1 < len(self.tick_end) else None

    def in_window(self, t: float) -> bool:
        return self.t_open <= t < self.t_close

    def check_ticks(self) -> None:
        """Every request's decode tokens lie on consecutive ticks, and no
        request streamed before it was due; else the tick count is wrong."""
        for rid, ticks in self.tick_of.items():
            dec = ticks[1:]
            if dec and dec != list(range(dec[0], dec[0] + len(dec))):
                raise RuntimeError(f"tick count lost: request {rid} decoded "
                                   f"on ticks {dec[:4]}...")
            if ticks[0] < self.stream.arrival[rid]:
                raise RuntimeError(
                    f"tick count lost: request {rid} streamed on tick "
                    f"{ticks[0]}, before its arrival tick "
                    f"{self.stream.arrival[rid]}")

    def tokens_in_window(self) -> int:
        return sum(1 for st in self.stamps.values() for t in st
                   if self.in_window(t))

    def ttfts(self) -> List[float]:
        """Due -> first token, seconds, for every request due in the window;
        one still waiting at the close counts with its wait so far."""
        out = []
        for rid, a in self.stream.arrival.items():
            d = self.due(rid)
            if d is None or not self.in_window(d):
                continue
            st = self.stamps.get(rid)
            first = st[0] if st and st[0] < self.t_close else self.t_close
            out.append(first - d)
        return out

    def itls(self) -> List[float]:
        """Gaps between consecutive tokens of a request, for every gap that
        ends in the window."""
        return [b - a for st in self.stamps.values()
                for a, b in zip(st, st[1:]) if self.in_window(b)]

    def finished(self) -> List[int]:
        """Requests that streamed all their tokens (the pacer left out)."""
        return [rid for rid, st in self.stamps.items()
                if rid != 0 and len(st) == self.stream.gen[rid]]

    def attempted(self) -> int:
        """Requests due in the window."""
        return sum(1 for rid in self.stream.arrival
                   if (d := self.due(rid)) is not None and self.in_window(d))

    def end_to_end(self) -> Dict[str, float]:
        tt, it = self.ttfts(), self.itls()
        return {
            "output_tok_s": self.tokens_in_window() / self.seconds,
            "ttft_p90_ms": percentile(tt, 90) * 1e3 if tt else math.nan,
            "itl_p90_ms": percentile(it, 90) * 1e3 if it else math.nan,
        }

    # -- per-layer counts ------------------------------------------------
    def decode_rows(self, t0: float, t1: float):
        """``(rid, context)`` of every decode-step token stamped in
        ``[t0, t1)``: the step attended ``prompt + tokens before it`` keys."""
        out = []
        for rid, st in self.stamps.items():
            lp = self.stream.prompt_len[rid]
            for j, t in enumerate(st):
                if j and t0 <= t < t1:
                    out.append((rid, lp + j))
        return out

    def decode_steps(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.tick_end if t0 <= t < t1)

    def queue_waits(self, chunk: int) -> List[int]:
        """Ticks from arrival to admission, for requests due in the window
        that streamed a first token: first-token tick less the ticks its
        chunks take (one chunk per tick)."""
        out = []
        for rid, ticks in self.tick_of.items():
            d = self.due(rid)
            if d is None or not self.in_window(d):
                continue
            chunks = -(-self.stream.prompt_len[rid] // chunk)
            out.append(ticks[0] - (chunks - 1) - self.stream.arrival[rid])
        return out
