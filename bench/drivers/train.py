"""Training through the program's own loop, with ``train_loss`` deploys.

Traffic keys: ``seq``, ``batch``; ``check_steps``, the set-up steps the
reference repeats; ``setup_z_coef``, where set, a z-loss module deployed
before those steps (fixed, so that every run finds its program in the
compile cache); ``deploys``: ``first_s``, ``every_s`` and
``z_coef_range``, a new z-loss module per deploy whose coefficient is
drawn from the seed and whose source carries a fresh nonce, so that no
compile cache has seen it (after such a window the check steps are run
again from the seed's state on the last deployed module, and those are
what the reference repeats); ``trace``: ``start_s`` and ``steps``, or
``around_deploy`` for the window part that ``--trace 1`` records.
"""
from __future__ import annotations

import dataclasses
import math
import os
import statistics
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, weights
from bench.reference import dense_lm

Z_LOSS = """import jax, jax.numpy as jnp
def run(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1).squeeze(-1)
    live = labels > -{nonce}
    return jnp.mean(logz - gold) + {coef!r} * jnp.mean(jnp.where(live, logz, 0.0) ** 2)
"""


def z_loss_source(coef: float, nonce: int = 1) -> str:
    """A z-loss module. ``labels > -nonce`` holds for every label, so the
    nonce changes the program's text and not its result."""
    return Z_LOSS.format(coef=float(coef), nonce=int(nonce))


class Driver:
    def __init__(self, cell: harness.Cell, seed: int, clog) -> None:
        self.cell, self.seed, self.clog = cell, seed, clog
        tr = cell.traffic
        self.B, self.S = tr["batch"], tr["seq"]
        self.rng = np.random.default_rng(weights.seed_words(seed, 4))
        self.steps: List[Dict] = []
        self.deploys: List[Dict] = []
        self.attempted = self.failed = 0
        self.trace = None
        self.t0 = self.t1 = 0.0

    # -- set-up -------------------------------------------------------------
    def setup(self) -> None:
        from repro.launch import train
        cfg, tr = self.cell.config, self.cell.traffic
        mc = harness.program_model(cfg)
        run = train.build_run(cfg["preset"], seq=self.S, batch=self.B)
        hp = cfg["train"]
        tc = dataclasses.replace(
            run.train, learning_rate=hp["learning_rate"],
            warmup_steps=hp["warmup_steps"], total_steps=hp["total_steps"],
            weight_decay=hp["weight_decay"], beta1=hp["beta1"],
            beta2=hp["beta2"], grad_clip=hp["grad_clip"], seed=self.seed)
        run = dataclasses.replace(run, model=mc, train=tc)
        self.run = run
        t = train.build_trainer(run)
        self.trainer = t
        m = self.cell.m
        params = weights.make_params(self.seed, m)
        if jax.tree.structure(params) != jax.tree.structure(t.state.params) \
                or jax.tree.leaves(jax.tree.map(
                    lambda a, b: a.shape != b.shape, params, t.state.params)).count(True):
            raise harness.BenchError("the program's parameter layout is not "
                                     "the checkpoint layout weights.shapes gives")
        state = t.state._replace(params=params)
        del params
        self.z_coef = 0.0
        if tr.get("setup_z_coef") is not None:
            self.z_coef = float(tr["setup_z_coef"])
            t.bindings["train_loss"].deploy(z_loss_source(self.z_coef))
        self.state = self._check_steps(state)

    def _check_steps(self, state):
        """The steps the reference repeats, from the seed's state through
        the loop's own call: their losses, the first gradient as Adam's
        first moment holds it, and the parameters' change after them."""
        t, n = self.trainer, self.cell.traffic["check_steps"]
        with harness.span("check_steps"):
            state = t.loop.run(state, 1)
            # Adam's first moment after one step is (1 - beta1) g
            b1 = self.cell.config["train"]["beta1"]
            self.first_grad = {k: v / (1 - b1) for k, v in
                               weights.leaf_norms(state.opt_state.mu).items()}
            state = t.loop.run(state, n - 1)
            self.change = weights.change_norms(state.params, self.seed,
                                               self.cell.m)
        hist = t.loop.history[-n:]
        self.check_losses = [h["loss"] for h in hist]
        self.check_md5s = [h["code_md5"]["train_loss"] for h in hist]
        return state

    def _check_last_deploy(self) -> None:
        """Repeats the check steps from the seed's state through the
        window's last program, the step with the last deployed
        ``train_loss``, so that the reference follows the code the
        window ended on. A step on another module counts as failed."""
        last = self.deploys[-1]
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            self.state._replace(params=None))
        self.state = None                 # freed before the fresh state
        zeros = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype, device=s.sharding), like)
        fresh = zeros._replace(
            params=weights.make_params(self.seed, self.cell.m))
        self.state = self._check_steps(fresh)
        self.z_coef = last["coef"]
        self.failed += sum(1 for x in self.check_md5s if x != last["md5"])

    # -- window -------------------------------------------------------------
    def window(self, seconds: float, tracer: harness.Tracer) -> None:
        tr, t = self.cell.traffic, self.trainer
        due = harness.deploy_times(self.cell.traffic, seconds)
        lo, hi = (tr.get("deploys") or {}).get("z_coef_range", (0, 0))
        coefs = [float(c) for c in self.rng.uniform(lo, hi, len(due))]
        nonce = 1 + int.from_bytes(os.urandom(4), "little") % (1 << 30)
        trace_cfg = tr.get("trace", {})
        around = bool(trace_cfg.get("around_deploy"))
        trace_steps = None
        state, tokens = self.state, self.B * self.S
        md5_live = t.loop.history[-1]["code_md5"]["train_loss"]
        pending: Optional[Dict] = None
        self.t0 = t_start = time.perf_counter()
        deadline = t_start + seconds
        while True:
            now = time.perf_counter()
            if now >= deadline:
                break
            el = now - t_start
            if tracer.on and tracer.started is None:
                if around and due and el >= due[0] - trace_cfg["lead_s"]:
                    tracer.start()
                elif not around and el >= trace_cfg["start_s"]:
                    tracer.start()
                    trace_steps = trace_cfg["steps"]
            if due and el >= due[0]:
                due.pop(0)
                with harness.span("deploy"):
                    k = len(self.deploys)
                    t_dep = time.perf_counter()
                    dep = t.bindings["train_loss"].deploy(
                        z_loss_source(coefs[k], nonce + k))
                pending = {"t_deploy": t_dep, "md5": dep.md5,
                           "coef": coefs[k], "t_effect": None}
                self.deploys.append(pending)
            t_step = time.perf_counter()
            with harness.span("train_step"):
                state = t.loop.run(state, 1)
            t_end = time.perf_counter()
            h = t.loop.history[-1]
            md5 = h["code_md5"]["train_loss"]
            rec = {"t0": t_step, "t1": t_end, "tokens": tokens,
                   "loss": h["loss"], "md5": md5}
            self.steps.append(rec)
            if pending is not None and md5 == pending["md5"]:
                pending["t_effect"] = t_end
                md5_live, pending = md5, None
                if around and tracer.started is not None \
                        and trace_steps is None:
                    trace_steps = trace_cfg["steps_after"] + 1
            elif md5 != md5_live:
                self.failed += 1          # a step on code nobody deployed
            if not math.isfinite(h["loss"]):
                self.failed += 1
            if trace_steps is not None:
                trace_steps -= 1
                if trace_steps <= 0:
                    tracer.stop()
                    trace_steps = None
        self.t1 = self.steps[-1]["t1"] if self.steps else time.perf_counter()
        tracer.stop()
        self.state = state
        self.attempted = len(self.steps)
        self.failed += sum(1 for d in self.deploys if d["t_effect"] is None)
        del state
        if self.deploys:
            self._check_last_deploy()

    # -- results ------------------------------------------------------------
    def end_to_end(self) -> Dict[str, float]:
        out = {"train_tokens_per_s": sum(s["tokens"] for s in self.steps)
               / (self.t1 - self.t0)}
        done = [d["t_effect"] - d["t_deploy"] for d in self.deploys
                if d["t_effect"] is not None]
        if done:
            out["swap_to_effect_s"] = statistics.fmean(done)
        return out

    def release(self) -> None:
        self.state = self.trainer = None

    def reference(self, mode: str = "f32", rows_used=None) -> Dict:
        """The reference's run of the set-up steps: losses, the first
        gradient per leaf as the optimizer gets it, and the parameters'
        change per leaf after them."""
        m, tr, hp = self.cell.m, self.cell.traffic, self.cell.config["train"]
        params0 = weights.make_params(self.seed, m)
        batches = [weights.affine_chain_rows(m["vocab"], self.S, self.B,
                                             self.seed, k)
                   for k in range(tr["check_steps"])]
        return dense_lm.train_steps(params0, batches, m, hp, self.z_coef,
                                    mode=mode, rows_used=rows_used)

    def readings(self) -> Dict:
        return {"losses": self.check_losses, "first_grad": self.first_grad,
                "change": self.change}

    def check(self) -> Dict[str, tuple]:
        """The program's check steps against the reference: the set-up's,
        or where the window deployed, those repeated after it."""
        return compare(self.readings(), self.reference(), self.cell.limits)


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: List[str]) -> float:
    """Worst leaf: |program norm - reference norm| over the larger of
    that leaf's reference norm and the median leaf's."""
    med = statistics.median(ref[k] for k in keep)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def kept_leaves(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at
    least a thousandth of the median leaf's."""
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= 1e-3 * med]


def compare(got: Dict, ref: Dict, lim: Dict) -> Dict[str, tuple]:
    """Each number compared, with its limit: the worst leaf of the first
    gradient and of the parameters' change, and the largest relative gap
    of a step's loss. A number with no limit in ``lim`` is read but not
    compared (its limit is None)."""
    keep = kept_leaves(ref["first_grad"])
    out = {
        "grad_rel": leaf_gap(got["first_grad"], ref["first_grad"], keep),
        "change_rel": leaf_gap(got["change"], ref["change"], keep),
        "loss_rel": max(abs(a - b) / abs(b)
                        for a, b in zip(got["losses"], ref["losses"])),
    }
    return {k: (v, lim.get(k)) for k, v in out.items()}
