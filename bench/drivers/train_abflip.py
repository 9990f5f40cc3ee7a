"""Training with deploys that flip between two ``train_loss`` versions
the step has already compiled.

The train driver's set-up, then two z-loss modules deployed once each,
with coefficients drawn from the seed, and one step run on each, so
that both executables are in ``HotSwapTrainStep._cache``. In the window
the deploys alternate between those two sources (the first one first)
every ``every_s`` from ``first_s``: each deploy redeploys known source,
so it should take effect on the next step with nothing compiled. The
check is the train driver's: the check steps repeated through the last
deployed module. Traffic keys are the train driver's, with ``deploys``
``first_s``, ``every_s`` and ``z_coef_range``.
"""
from __future__ import annotations

import math
import time
from typing import Dict, Optional

from bench import harness
from bench.drivers import train


class Driver(train.Driver):
    def setup(self) -> None:
        super().setup()
        lo, hi = self.cell.traffic["deploys"]["z_coef_range"]
        self.flip = [(float(c), train.z_loss_source(c))
                     for c in self.rng.uniform(lo, hi, 2)]
        slot = self.trainer.bindings["train_loss"]
        with harness.span("warm_up"):
            for _, source in self.flip:
                slot.deploy(source)
                self.state = self.trainer.loop.run(self.state, 1)

    def window(self, seconds: float, tracer: harness.Tracer) -> None:
        t, tc = self.trainer, self.cell.traffic["trace"]
        due = harness.deploy_times(self.cell.traffic, seconds)
        state, tokens = self.state, self.B * self.S
        md5_live = t.loop.history[-1]["code_md5"]["train_loss"]
        pending: Optional[Dict] = None
        trace_steps = None
        self.t0 = t_start = time.perf_counter()
        deadline = t_start + seconds
        while time.perf_counter() < deadline:
            el = time.perf_counter() - t_start
            if tracer.on and tracer.started is None and due \
                    and el >= due[0] - tc["lead_s"]:
                tracer.start()
            if due and el >= due[0]:
                due.pop(0)
                coef, source = self.flip[len(self.deploys) % 2]
                with harness.span("deploy"):
                    t_dep = time.perf_counter()
                    dep = t.bindings["train_loss"].deploy(source)
                pending = {"t_deploy": t_dep, "md5": dep.md5, "coef": coef,
                           "t_effect": None}
                self.deploys.append(pending)
            t_step = time.perf_counter()
            with harness.span("train_step"):
                state = t.loop.run(state, 1)
            t_end = time.perf_counter()
            h = t.loop.history[-1]
            md5 = h["code_md5"]["train_loss"]
            self.steps.append({"t0": t_step, "t1": t_end, "tokens": tokens,
                               "loss": h["loss"], "md5": md5})
            if pending is not None and md5 == pending["md5"]:
                pending["t_effect"] = t_end
                md5_live, pending = md5, None
                if tracer.started is not None and trace_steps is None:
                    trace_steps = tc["steps_after"] + 1
            elif md5 != md5_live:
                self.failed += 1          # a step on code nobody deployed
            if not math.isfinite(h["loss"]):
                self.failed += 1
            if trace_steps is not None:
                trace_steps -= 1
                if trace_steps <= 0:
                    tracer.stop()
        self.t1 = self.steps[-1]["t1"] if self.steps else time.perf_counter()
        tracer.stop()
        self.state = state
        self.attempted = len(self.steps)
        self.failed += sum(1 for d in self.deploys if d["t_effect"] is None)
        del state
        if self.deploys:
            self._check_last_deploy()
