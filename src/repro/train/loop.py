"""Host-side training loop: data feed, hot-swap boundary, checkpointing,
preemption handling.

The loop is where the paper's "reload the custom module with each
iteration" lives: every step re-resolves the slot bindings (an integer
epoch compare when nothing changed) before dispatching the jitted step.
A deploy that lands mid-step takes effect on the next step — no restart,
no disruption to the in-flight computation.
"""
from __future__ import annotations

import signal
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint.store import CheckpointStore
from repro.configs.base import RunConfig
from repro.core.telemetry import Metrics, timed
from repro.data.synthetic import SyntheticTask, batch_at
from repro.train.state import TrainState
from repro.train.step import HotSwapTrainStep


@dataclass
class TrainLoop:
    step_fn: HotSwapTrainStep
    task: SyntheticTask
    run_cfg: RunConfig
    store: Optional[CheckpointStore] = None
    ckpt_every: int = 0
    log_every: int = 10
    history: List[Dict[str, Any]] = field(default_factory=list)
    _preempted: bool = False

    @property
    def metrics(self) -> Metrics:
        """The step's ``Metrics``, which the loop's ``train.batch`` span
        histogram shares."""
        return self.step_fn.metrics

    def install_sigterm_save(self) -> None:
        def handler(signum, frame):
            self._preempted = True
        signal.signal(signal.SIGTERM, handler)

    def run(self, state: TrainState, n_steps: int,
            on_step: Optional[Callable[[int, Dict[str, Any]], None]] = None
            ) -> TrainState:
        start = int(state.step)
        for i in range(start, start + n_steps):
            with timed(self.metrics, "train.batch"):
                batch = batch_at(self.task, i)
            t0 = time.perf_counter()
            state, metrics = self.step_fn(state, batch)
            metrics = {
                k: (float(v) if hasattr(v, "item") and getattr(v, "ndim", 1) == 0
                    else v)
                for k, v in metrics.items()}
            metrics["step"] = i
            metrics["step_ms"] = (time.perf_counter() - t0) * 1e3
            self.history.append(metrics)
            if on_step is not None:
                on_step(i, metrics)
            if self.ckpt_every and self.store and (i + 1) % self.ckpt_every == 0:
                self.store.save(state, step=i + 1)
            if self._preempted:
                if self.store:
                    self.store.save(state, step=i + 1, tag="preempt")
                break
        return state
