"""Fault tolerance & elasticity for 1000+-node synchronous SPMD training.

Policy (DESIGN.md §5):

* **Checkpoint/restart** is the recovery primitive.  Steps are fenced by
  atomic checkpoint commits (checkpoint/store.py); the data pipeline is a
  pure function of (seed, step) (data/pipeline.py) — so a restart resumes
  bitwise-identically from the last commit.  ``TrainingRunner.run`` is a
  crash-only loop: any exception falls back to restore-latest-and-continue,
  bounded by ``max_restarts``.

* **Straggler mitigation**: under synchronous SPMD a straggling *chip* stalls
  the whole step, so mitigation is detect-and-evict, not work-stealing (which
  would break the paper's static process↔data analyzability).  The
  ``StepWatchdog`` tracks a robust step-time estimate (median + MAD); a step
  exceeding ``k`` MADs raises a straggler event, and the runner responds by
  checkpointing and requesting a reschedule (on a real cluster: replace the
  node, here: restart the loop).

* **Elastic scaling**: ``ElasticPlan`` recomputes the mesh for a new chip
  count.  Because params/opt are saved as logical arrays and resharded on
  restore (restore_checkpoint with a new sharding tree), shrinking/growing
  the ``data`` axis needs no format change; the batch iterator re-derives
  per-host slices from global indices.  The ``model`` axis is fixed per
  config (TP degree is architectural), so elasticity acts on data/pod axes.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional

import jax
import numpy as np

from repro import checkpoint as ckpt
from repro.core.compat import make_mesh


class StepWatchdog:
    """Robust step-time anomaly detector (median + k·MAD)."""

    def __init__(self, k: float = 6.0, window: int = 50, min_steps: int = 10):
        self.k, self.window, self.min_steps = k, window, min_steps
        self.times: List[float] = []

    def observe(self, dt: float) -> bool:
        """Record a step time; returns True if it's a straggler event."""
        self.times.append(dt)
        self.times = self.times[-self.window:]
        if len(self.times) < self.min_steps:
            return False
        med = float(np.median(self.times))
        mad = float(np.median(np.abs(np.array(self.times) - med))) + 1e-9
        return dt > med + self.k * mad


@dataclass
class ElasticPlan:
    """Mesh plan for a given healthy-chip count."""
    model: int = 16
    min_data: int = 1

    def mesh_for(self, n_chips: int, devices=None):
        data = max(self.min_data, n_chips // self.model)
        shape, axes = (data, self.model), ("data", "model")
        if devices is not None:
            devices = devices[: data * self.model]
        return make_mesh(shape, axes, devices=devices)


@dataclass
class TrainingRunner:
    """Crash-only training loop: restore → run → (fault) → restore → ...

    ``build`` re-creates (state, step_fn, batch_iter) from a step index —
    called at start and after every recovery, so device placement and the
    data stream are always reconstructed from durable state only.
    """
    directory: str
    build: Callable[[int], tuple]           # step -> (state, step_fn, batches)
    checkpoint_every: int = 100
    max_restarts: int = 3
    watchdog: StepWatchdog = field(default_factory=StepWatchdog)

    def run(self, total_steps: int, *, inject_fault_at: Optional[int] = None):
        """Returns (final_state, metrics_history).  ``inject_fault_at`` is the
        test hook proving recovery (tests/test_runtime.py)."""
        restarts = 0
        history = []
        saver = ckpt.AsyncCheckpointer(self.directory)
        while True:
            start = ckpt.latest_step(self.directory) or 0
            state, step_fn, batches = self.build(start)
            step = start
            try:
                for batch in batches:
                    if step >= total_steps:
                        saver.wait()
                        return state, history
                    t0 = time.perf_counter()
                    if inject_fault_at is not None and step == inject_fault_at:
                        inject_fault_at = None  # fire once
                        raise RuntimeError("injected node failure")
                    state, metrics = step_fn(state, batch)
                    jax.block_until_ready(metrics["loss"])
                    dt = time.perf_counter() - t0
                    straggler = self.watchdog.observe(dt)
                    history.append({"step": step, "time_s": dt,
                                    **{k: float(v) for k, v in metrics.items()}})
                    step += 1
                    if step % self.checkpoint_every == 0:
                        saver.save(step, state)
                    if straggler:
                        raise RuntimeError(f"straggler step {step - 1}: {dt:.3f}s")
            except (RuntimeError, jax.errors.JaxRuntimeError) as e:
                restarts += 1
                if restarts > self.max_restarts:
                    raise
                saver.wait()
                # recovery: loop re-enters, restores latest commit, rebuilds
                continue
