"""Chip smoke: the hot-swap train, serve and fleet paths once on one TPU.

    python3 chip_smoke.py

Three phases run in this one process, which holds the chip; nothing is
spawned. Each drives the entry points a user calls:

1. train: smollm-135m at its published widths (30 layers, d_model 576,
   vocab 49152) through ``repro.launch.train``'s builders
   (``HotSwapTrainStep`` + ``TrainLoop``) at batch 8 x 1024 tokens.
   A new ``train_loss`` is deployed through the registry between
   steps. The first step's loss agrees with an XLA-only forward
   (no Pallas kernels) on the same parameters and batch.
2. serve: ``ServeEngine`` on the same model, batch 4; a temperature
   sampler is deployed mid-generation, then rolled back, and the
   greedy prefix is generated again.
3. fleet: the in-process fleet (``Fleet.create(8)``) runs the README's
   deploy -> iterate -> hot-swap -> rollback -> cancel flow with a
   ``jax.numpy`` module whose arrays live on the chip.

Each phase prints one line of smoke readings (step times, tokens per
second, compile seconds, peak device bytes): they say the path runs,
they are not benchmark results. The last line is
``{"ok": true, "device": {...}}``. On a backend other than a TPU, or
when any phase fails, the script exits non-zero and prints no such
line. The compile cache is ``repro.launch.cache``'s.
"""
from __future__ import annotations

import dataclasses
import json
import math
import statistics
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import IterationEvent, Status  # noqa: E402
from repro.core.fleet import Fleet  # noqa: E402
from repro.data.synthetic import batch_at  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.cache import setup_compile_cache  # noqa: E402
from repro.train.step import default_loss, model_forward  # noqa: E402

ARCH = "smollm-135m"

Z_LOSS = """
import jax, jax.numpy as jnp
def run(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)
    return jnp.mean(logz - gold.squeeze(-1)) + 1e-4 * jnp.mean(logz ** 2)
"""

RANGE_SLOT = "smoothed_range"


def _range_module(hi: int, lo: int) -> str:
    # keeps its last array in the client's per-method state, where the
    # fleet phase reads back which device it lives on
    return f"""
import jax.numpy as jnp
def run(xs, ctx):
    w = jnp.asarray(xs)
    y = jnp.percentile(w, {hi}) - jnp.percentile(w, {lo})
    ctx["state"]["last"] = y
    return y
"""


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spends in backend compiles (reads of the persistent
    compile cache included) and the number of persistent-cache hits,
    while the ``with`` block runs."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()

    def _duration(self, event: str, duration: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration

    def _event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc: Any) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def train_phase(*, reduced: bool = False, batch: int = 8, seq: int = 1024,
                steps: int = 6, swap_after: int = 3) -> Dict[str, Any]:
    """``steps`` steps of the hot-swap train step, with a ``train_loss``
    deploy after ``swap_after`` of them."""
    _check(1 <= swap_after <= steps - 2, "need steady steps on both sides "
           "of the swap")
    run = train.build_run(ARCH, reduced=reduced, seq=seq, batch=batch,
                          steps=steps)
    t = train.build_trainer(run)
    step = t.step

    # reference: the same first batch through an XLA-only forward
    batch0 = batch_at(t.loop.task, int(t.state.step))
    ctx_xla = dataclasses.replace(step.ctx, norm_impl="xla")
    ref_loss = float(jax.jit(lambda p, b: default_loss(
        model_forward(step.model, p, b, ctx_xla)[0], b["labels"]))(
            t.state.params, batch0))

    state = t.loop.run(t.state, swap_after)
    rebuilds = step.rebuilds
    dep = t.bindings["train_loss"].deploy(Z_LOSS)
    t.loop.run(state, steps - swap_after)

    hist = t.loop.history
    md5s = [h["code_md5"]["train_loss"] for h in hist]
    losses = [h["loss"] for h in hist]
    _check(md5s == ["builtin"] * swap_after + [dep.md5] * (steps - swap_after),
           f"train_loss md5 per step {md5s}, swap to {dep.md5}")
    _check(step.rebuilds == rebuilds + 1,
           f"rebuilds {rebuilds} -> {step.rebuilds}, want one more")
    _check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    _check(abs(losses[0] - ref_loss) <= 2e-2 * abs(ref_loss),
           f"first loss {losses[0]} vs XLA-only forward {ref_loss}")

    ms = [h["step_ms"] for h in hist]
    steady_ms = statistics.median(
        m for i, m in enumerate(ms) if i not in (0, swap_after))
    return {
        "phase": "train", "arch": run.model.name,
        "layers": run.model.num_layers, "d_model": run.model.d_model,
        "vocab": run.model.vocab_size, "batch": batch, "seq": seq,
        "losses": losses, "ref_loss": ref_loss, "step_ms": ms,
        "steady_step_ms": steady_ms,
        "tokens_per_s": batch * seq / (steady_ms / 1e3),
        "swap_md5": dep.md5, "rebuilds": step.rebuilds,
    }


def serve_phase(*, reduced: bool = False, batch: int = 4,
                prompt_len: int = 64, tokens: int = 16,
                max_seq: int = 1024) -> Dict[str, Any]:
    """Greedy generation with a temperature sampler deployed part-way,
    then a rollback and a second, all-greedy generation."""
    run = serve.build_run(ARCH, reduced=reduced, batch=batch,
                          max_seq=max_seq)
    engine, params = serve.build_server(run)
    vocab = run.model.vocab_size
    prompt = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt_len),
                                0, vocab)
    v1 = engine.deploy_sampler(serve.GREEDY_SAMPLER)
    swap_at = tokens // 2 - 1
    swapped = []

    def on_token(i: int, tok: jax.Array) -> None:
        if i == swap_at:
            swapped.append(engine.deploy_sampler(
                serve.temperature_sampler_source(0.8)))

    t0 = time.perf_counter()
    toks, info = engine.generate(params, prompt, tokens, on_token=on_token)
    toks = np.asarray(toks)
    first_s = time.perf_counter() - t0
    _check(len(swapped) == 1, "sampler deploy did not happen")
    v2 = swapped[0]
    # the token decoded right after the deploy is the first on v2
    flip = swap_at + 2
    want = [v1.md5] * flip + [v2.md5] * (tokens - flip)
    _check(info["sampler_md5s"] == want,
           f"sampler md5 per token {info['sampler_md5s']}")
    _check(toks.shape == (batch, tokens)
           and int(toks.min()) >= 0 and int(toks.max()) < vocab,
           f"tokens {toks.shape} in [{toks.min()}, {toks.max()}]")

    rebuilds = engine.rebuilds
    restored = v2.rollback()
    _check(restored.md5 == v1.md5, "rollback did not restore v1")
    t0 = time.perf_counter()
    toks2, info2 = engine.generate(params, prompt, tokens)
    toks2 = np.asarray(toks2)
    again_s = time.perf_counter() - t0
    _check(set(info2["sampler_md5s"]) == {v1.md5},
           f"after rollback: {set(info2['sampler_md5s'])}")
    _check(engine.rebuilds == rebuilds,
           f"rollback re-jitted: {rebuilds} -> {engine.rebuilds}")
    _check(np.array_equal(toks2[:, :flip], toks[:, :flip]),
           "greedy prefix differs between two generations")
    return {
        "phase": "serve", "batch": batch, "prompt_len": prompt_len,
        "tokens": tokens, "max_seq": max_seq, "swap_after_token": flip,
        "first_generate_s": first_s, "rollback_generate_s": again_s,
        "tokens_per_s": batch * tokens / again_s,
        "sampler_md5s": [v1.md5, v2.md5], "rebuilds": engine.rebuilds,
    }


def fleet_phase(*, n_clients: int = 8, timeout_s: float = 120.0
                ) -> Dict[str, Any]:
    """Deploy -> iterate -> hot-swap -> rollback -> cancel on the
    in-process fleet; every client's last array is on this process's
    default device."""
    fleet = Fleet.create(n_clients=n_clients, seed=0)
    try:
        analyst = fleet.frontend("analyst-1")
        t0 = time.perf_counter()
        v1 = analyst.deploy_code(RANGE_SLOT, _range_module(90, 10))
        _, done = v1.result(timeout_s)
        deploy_s = time.perf_counter() - t0
        _check(done.status == Status.DONE, f"deploy v1: {done.status}")

        handle = analyst.submit_analytics(RANGE_SLOT, iterations=1_000_000,
                                          params={"n_values": 128})
        stream = handle.events(timeout=timeout_s)

        def until(md5: str) -> None:
            for ev in stream:
                if isinstance(ev, IterationEvent) and ev.winning_md5 == md5:
                    return
            raise SmokeFailure(f"stream ended before version {md5[:8]}")

        until(v1.md5)
        t0 = time.perf_counter()
        v2 = analyst.deploy_code(RANGE_SLOT, _range_module(75, 25))
        v2.result(timeout_s)
        until(v2.md5)
        swap_s = time.perf_counter() - t0
        back = v2.rollback()
        back.result(timeout_s)
        until(v1.md5)
        handle.cancel()
        iters, done = handle.result(timeout_s)
        _check(done.status == Status.CANCELLED, f"cancel: {done.status}")

        order = [v1.md5, v2.md5, v1.md5]
        seen = [ev.winning_md5 for ev in iters if ev.winning_md5 in order]
        runs = [m for i, m in enumerate(seen) if i == 0 or seen[i - 1] != m]
        _check(runs[:3] == order, f"versions in order {runs}")
        _check(back.md5 == v1.md5, "rollback did not restore v1")

        want = jax.devices()[0]
        placed = {cid: {d.platform for d in
                        app.method_state[RANGE_SLOT]["last"].devices()}
                  for cid, app in fleet.client_apps.items()}
        _check(all(p == {want.platform} for p in placed.values()),
               f"client arrays on {placed}, want {want.platform}")
        return {
            "phase": "fleet", "clients": n_clients, "iterations": len(iters),
            "deploy_s": deploy_s, "swap_to_effect_s": swap_s,
            "client_array_platform": want.platform,
        }
    finally:
        fleet.shutdown()


# ---------------------------------------------------------------------------

def _peak_bytes() -> Any:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main() -> int:
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend {backend!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    setup_compile_cache()
    dev = jax.devices()[0]
    for phase in (train_phase, serve_phase, fleet_phase):
        with CompileClock() as clock:
            t0 = time.perf_counter()
            try:
                readings = phase()
            except Exception as e:  # noqa: BLE001 - report, then fail
                print(f"chip_smoke: {phase.__name__} failed: "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
                raise
            readings["wall_s"] = time.perf_counter() - t0
        readings["compile_s"] = clock.seconds
        readings["cache_hits"] = clock.cache_hits
        readings["peak_bytes_in_use"] = _peak_bytes()
        print("smoke reading (not a benchmark result): "
              + json.dumps(readings), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
