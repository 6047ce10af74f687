"""The serving engine's telemetry: phase spans on the profiler's clock and a
bounded, process-wide log of per-tick counts.

Spans.  ``Scheduler.run`` tiles every engine tick with flat, non-nesting
``jax.profiler.TraceAnnotation`` spans, each carrying ``tick=<n>``:

  ``serve.admit``     the admission loop (in the end-aligned engine with
                      each prompt's prefill and the wait for its first token)
  ``serve.prefill``   the paged engine's prefill chunk calls
  ``serve.pick``      the wait for the first tokens of the prompts those
                      chunks completed
  ``serve.prepare``   page growth, table refresh, host -> device inputs
  ``serve.dispatch``  the decode step's call
  ``serve.sync``      the wait for the decode step's tokens
  ``serve.emit``      the token callbacks and evictions, after the pick and
                      after the sync

and ``gc_spans`` adds ``serve.gc`` around each collector pause while a run is
active.  The spans land in the same trace as the device operations, so they
share its clock; no span encloses a whole tick, so a device-idle stretch is
named after the phase the host was in.  ``serve.pick`` and ``serve.sync`` are
the host waiting on the device; the others are the host's own work.  With the
profiler off a span costs under a microsecond of host time.

Tick log.  One ``Tick`` per engine pass that did work, stamped with
``time.perf_counter()`` at the decode step's sync (at the tick's end on a
tick that decodes nothing).  Records hold only numbers, never a device array,
and the log keeps the newest ``MAX_TICKS``.  It is process-wide so that a
reader can find it after the engine is gone: ``records(t0, t1)``.
"""
from __future__ import annotations

import contextlib
import gc
from collections import deque
from typing import Deque, Iterator, List, NamedTuple

from jax.profiler import TraceAnnotation

MAX_TICKS = 65536


class Tick(NamedTuple):
    t: float                    # time.perf_counter() at the tick's sync
    tick: int
    chunks: int                 # prefill chunk calls
    chunk_tokens: int           # prompt tokens those chunks consumed
    reserved_pages: int         # BlockPool.reserved_blocks at the sync
    written_pages: int          # BlockPool.live_blocks at the sync


LOG: Deque[Tick] = deque(maxlen=MAX_TICKS)


def records(t0: float = float("-inf"), t1: float = float("inf")) -> List[Tick]:
    """The logged ticks stamped in ``[t0, t1)``, oldest first."""
    return [r for r in LOG if t0 <= r.t < t1]


@contextlib.contextmanager
def gc_spans() -> Iterator[None]:
    """While active, each collector pause is a ``serve.gc`` span."""
    open_: List[TraceAnnotation] = []

    def hook(phase: str, info: dict) -> None:
        if phase == "start":
            ann = TraceAnnotation("serve.gc", generation=info["generation"])
            ann.__enter__()
            open_.append(ann)
        elif open_:
            open_.pop().__exit__(None, None, None)

    gc.callbacks.append(hook)
    try:
        yield
    finally:
        gc.callbacks.remove(hook)
        while open_:
            open_.pop().__exit__(None, None, None)
