"""Closed-loop batched generation through the program's serving engine.

One client keeps ``ServeEngine.generate`` busy: each call takes
``batch`` prompts of ``prompt_len`` ids drawn from the seed and
generates ``new_tokens`` per prompt into a cache of ``max_seq``. Every
decoded token is fetched to the host as it arrives, as a streaming
client would. Traffic keys besides those: ``check_rows``, how many
greedy rows the reference repeats; ``embed_scale``, the factor on the
tied embedding's spread in the weights drawn from the seed (below 1 the
last input token stops dominating its own next-token logits, so that
top-1 margins are small enough for rounding to flip some of them); ``deploys``: ``first_s``,
``every_s``, ``temperature_range``, an A/B sampler per deploy that
samples odd rows at a temperature drawn from the seed and keeps even
rows greedy, with a fresh nonce so that no compile cache has seen it;
``trace``: ``start_s`` and ``seconds``, or ``around_deploy`` with
``lead_s`` and ``tokens_after``.
"""
from __future__ import annotations

import dataclasses
import os
import statistics
import time
from typing import Dict, List

import jax
import numpy as np

from bench import harness, weights
from bench.reference import dense_lm

AB_SAMPLER = """import jax, jax.numpy as jnp
def run(logits, key):
    greedy = jnp.argmax(logits, axis=-1).astype('int32')
    drawn = jax.random.categorical(key, logits / {temp!r}).astype('int32')
    rows = jnp.arange(logits.shape[0])
    return jnp.where((rows % 2 == 0) | (rows < -{nonce}), greedy, drawn)
"""


def ab_sampler_source(temp: float, nonce: int = 1) -> str:
    """Even rows greedy, odd rows at ``temp``. ``rows < -nonce`` never
    holds, so the nonce changes the program's text and not its tokens."""
    return AB_SAMPLER.format(temp=float(temp), nonce=int(nonce))


class StopWindow(Exception):
    pass


class Driver:
    def __init__(self, cell: harness.Cell, seed: int, clog) -> None:
        self.cell, self.seed, self.clog = cell, seed, clog
        tr = cell.traffic
        self.B, self.P, self.N = tr["batch"], tr["prompt_len"], tr["new_tokens"]
        self.embed_scale = float(tr.get("embed_scale", 1.0))
        self.rng = np.random.default_rng(weights.seed_words(seed, 4))
        self.calls: List[Dict] = []
        self.deploys: List[Dict] = []
        self.attempted = self.failed = 0
        self.trace = None
        self.t0 = self.t1 = 0.0

    def _prompts(self) -> np.ndarray:
        return self.rng.integers(0, self.cell.m["vocab"], (self.B, self.P),
                                 dtype=np.int32)

    def setup(self) -> None:
        from repro.launch import serve
        cfg, tr = self.cell.config, self.cell.traffic
        mc = harness.program_model(cfg)
        run = serve.build_run(cfg["preset"], batch=self.B,
                              max_seq=tr["max_seq"])
        run = dataclasses.replace(run, model=mc)
        engine, prog_params = serve.build_server(run)
        del prog_params
        self.engine = engine
        self.params = weights.make_params(self.seed, self.cell.m,
                                          self.embed_scale)
        self.greedy = engine.deploy_sampler(serve.GREEDY_SAMPLER)
        with harness.span("warm_up"):
            toks, _ = engine.generate(
                self.params, jax.numpy.zeros((self.B, self.P), np.int32), self.N)
            np.asarray(toks)

    def window(self, seconds: float, tracer: harness.Tracer) -> None:
        tr, engine = self.cell.traffic, self.engine
        due = harness.deploy_times(self.cell.traffic, seconds)
        lo, hi = (tr.get("deploys") or {}).get("temperature_range", (1, 1))
        temps = [float(x) for x in self.rng.uniform(lo, hi, len(due))]
        nonce = 1 + int.from_bytes(os.urandom(4), "little") % (1 << 30)
        tc = tr.get("trace", {})
        around = bool(tc.get("around_deploy"))
        state = {"stop_after": None}
        self.t0 = t_start = time.perf_counter()
        deadline = t_start + seconds

        def on_token(i: int, tok) -> None:
            with harness.span("token_fetch"):
                np.asarray(tok)
            now = time.perf_counter()
            call["arrivals"].append(now)
            el = now - t_start
            for d in self.deploys:
                if d["t_effect"] is None and d["call"] == len(self.calls) \
                        and i == d["index"] + 1:
                    d["t_effect"] = now
                    if around and tracer.started is not None:
                        state["stop_after"] = now + tc["seconds_after"]
            if state["stop_after"] is not None and now >= state["stop_after"]:
                tracer.stop()
            if tracer.on and tracer.started is None:
                if around and due and el >= due[0] - tc["lead_s"]:
                    tracer.start()
                elif not around and el >= tc["start_s"]:
                    tracer.start()
                    state["stop_after"] = now + tc["seconds"]
            if now >= deadline:
                raise StopWindow
            if due and el >= due[0] and i < self.N - 3:
                due.pop(0)
                k = len(self.deploys)
                with harness.span("deploy"):
                    t_dep = time.perf_counter()
                    dep = engine.deploy_sampler(
                        ab_sampler_source(temps[k], nonce + k))
                self.deploys.append({"t_deploy": t_dep, "md5": dep.md5,
                                     "temp": temps[k], "call": len(self.calls),
                                     "index": i, "t_effect": None})

        while time.perf_counter() < deadline:
            prompts = self._prompts()
            call = {"t_start": time.perf_counter(), "arrivals": [],
                    "prompts": prompts, "tokens": None, "md5s": None,
                    "first_md5": None}
            md5_before = self._live_md5()
            try:
                with harness.span("generate"):
                    toks, info = engine.generate(self.params,
                                                 jax.numpy.asarray(prompts),
                                                 self.N, on_token=on_token)
                    call["tokens"] = np.asarray(toks)
                call["md5s"] = info["sampler_md5s"]
                call["first_md5"] = md5_before
                self.calls.append(call)
            except StopWindow:
                self.calls.append(call)
                break
        self.t1 = max(c["arrivals"][-1] for c in self.calls if c["arrivals"])
        tracer.stop()

    def _live_md5(self) -> str:
        return self.engine.sampler_binding.current().md5

    # -- results ------------------------------------------------------------
    def end_to_end(self) -> Dict[str, float]:
        fetched = sum(len(c["arrivals"]) for c in self.calls)
        firsts = sum(1 for c in self.calls if c["tokens"] is not None)
        out = {"decode_tokens_per_s":
               self.B * (fetched + firsts) / (self.t1 - self.t0)}
        done = [d["t_effect"] - d["t_deploy"] for d in self.deploys
                if d["t_effect"] is not None]
        if done:
            out["swap_to_effect_s"] = statistics.fmean(done)
        return out

    def gaps_ms(self) -> List[float]:
        """Every gap between consecutive fetched tokens of a sequence."""
        return [1e3 * (b - a) for c in self.calls
                for a, b in zip(c["arrivals"], c["arrivals"][1:])]

    def release(self) -> None:
        self.engine = self.params = None

    def _audit(self) -> List[tuple]:
        """Every finished call's tokens and sampler versions; returns the
        (call, row) pairs whose every token is greedy."""
        V = self.cell.m["vocab"]
        greedy_rows = []
        ab = {d["md5"] for d in self.deploys}
        for ci, c in enumerate(self.calls):
            self.attempted += self.B
            if c["tokens"] is None:
                continue
            ok = c["md5s"][0] == c["first_md5"]
            for d in self.deploys:
                if d["call"] == ci:
                    j = d["index"] + 2          # first token on the new code
                    ok &= c["md5s"][j] == d["md5"] and c["md5s"][j - 1] != d["md5"]
            ok &= all(x in ab | {self.greedy.md5} for x in c["md5s"])
            toks = c["tokens"]
            ok &= toks.shape == (self.B, self.N) and int(toks.min()) >= 0 \
                and int(toks.max()) < V
            if not ok:
                self.failed += self.B
                continue
            all_greedy = all(x == self.greedy.md5 for x in c["md5s"])
            greedy_rows += [(ci, r) for r in range(self.B)
                            if all_greedy or r % 2 == 0]
        return greedy_rows

    def sample(self) -> List[tuple]:
        """Greedy (call, row) pairs drawn from the seed, once the window's
        calls have been audited."""
        rows = self._audit()
        n = min(self.cell.traffic["check_rows"], len(rows))
        rng = np.random.default_rng(weights.seed_words(self.seed, 3))
        return [rows[j] for j in rng.choice(len(rows), n, replace=False)]

    def widest_gap(self, picks: List[tuple], mode: str = "f32") -> float:
        """The widest gap by which a token's logit lies below the f32
        reference's best: the served tokens', or with another ``mode``
        the tokens that mode puts first at the same positions."""
        params = weights.make_params(self.seed, self.cell.m, self.embed_scale)
        widest = 0.0
        for ci, r in picks:
            c = self.calls[ci]
            gaps = dense_lm.served_gaps(params, c["prompts"][r],
                                        c["tokens"][r], self.cell.m, mode)
            widest = max(widest, float(gaps.max()))
        return widest

    def check(self) -> Dict[str, tuple]:
        picks = self.sample()
        gap = self.widest_gap(picks) if picks else float("inf")
        return {"logit_gap": (gap, self.cell.limits["logit_gap"])}
