"""Spans, counters and rebuild records of the hot-swap train and serve
paths, and the named scopes of their jitted steps."""
import dataclasses
import glob
import re
import threading
import time

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs import make_run_config
from repro.core.registry import ActiveCodeRegistry
from repro.core.telemetry import (
    COMPILE_EVENTS,
    Metrics,
    compile_listener,
    rebuild_span,
    timed,
)
from repro.core.tracing import SpanRecorder
from repro.data.synthetic import batch_at, make_task
from repro.models import build_model
from repro.optim.api import build_optimizer
from repro.serve.engine import ServeEngine
from repro.train import HotSwapTrainStep, TrainLoop, init_state

LOSS = """
import jax, jax.numpy as jnp
def run(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)
    return jnp.mean(logz - gold.squeeze(-1)) * {scale}
"""
SAMPLER = """
import jax, jax.numpy as jnp
def run(logits, key):
    return jnp.argmax(logits - {shift}, axis=-1).astype(jnp.int32)
"""
TRAIN_SPANS = {"train.batch", "train.resolve", "train.rebuild"}
SERVE_SPANS = {"serve.prefill", "serve.first_token", "serve.resolve",
               "serve.rebuild"}


def trainer(async_compile=False):
    run = make_run_config("smollm-135m", "train_4k")
    run = dataclasses.replace(
        run, model=run.model.reduced(),
        shape=dataclasses.replace(run.shape, seq_len=32, global_batch=2),
        train=dataclasses.replace(run.train, num_microbatches=1))
    model = build_model(run.model)
    opt = build_optimizer(run.train, run.model.param_dtype)
    state = init_state(model, opt, jax.random.PRNGKey(0), run)
    reg = ActiveCodeRegistry()
    bindings = {s: reg.bind("u", s) for s in HotSwapTrainStep.SLOTS}
    step = HotSwapTrainStep(model, run, opt, bindings,
                            async_compile=async_compile)
    task = make_task(run.model.vocab_size, 32, 2, seed=0)
    return state, bindings, step, TrainLoop(step, task, run)


def server():
    run = make_run_config("qwen3-0.6b", "decode_32k")
    run = dataclasses.replace(
        run, model=run.model.reduced(),
        shape=dataclasses.replace(run.shape, seq_len=32, global_batch=2))
    model = build_model(run.model)
    params = model.init(jax.random.PRNGKey(0))
    binding = ActiveCodeRegistry().bind("u", "sampler")
    engine = ServeEngine(model, run, sampler_binding=binding)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                run.model.vocab_size)
    return engine, binding, params, prompt


def has_scope(text, scope):
    """``scope`` is a component of some name stack among the lowered
    text's locations, on its own or inside a transformation such as
    ``jvp(loss)``."""
    return re.search(rf'[/("]{scope}[/)":]', text) is not None


def records(spans, name):
    return [s["attrs"] for s in spans.drain() if s["name"] == name]


# ---------------------------------------------------------------------------

def test_spans_appear_in_the_profiler_trace(tmp_path):
    state, bindings, step, loop = trainer()
    engine, _, params, prompt = server()
    jax.profiler.start_trace(str(tmp_path))
    try:
        state = loop.run(state, 2)
        engine.generate(params, prompt, 3)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    names = {e.name for p in ProfileData.from_file(path).planes
             for ln in p.lines for e in ln.events}
    ours = {n for n in names if n.startswith("repro.")}
    # the leaves and nothing else: no span encloses a whole step or token
    assert ours == {"repro." + s for s in TRAIN_SPANS | SERVE_SPANS}


def test_histogram_counts_equal_steps_and_tokens():
    state, _, step, loop = trainer()
    assert loop.metrics is step.metrics
    loop.run(state, 3)
    h = step.metrics.histograms()
    assert h["train.batch"]["count"] == 3
    assert h["train.resolve"]["count"] == 3
    assert h["train.rebuild"]["count"] == 1
    assert all(v["min"] > 0 for v in h.values())
    engine, _, params, prompt = server()
    engine.generate(params, prompt, 5)
    engine.generate(params, prompt, 4)
    h = engine.metrics.histograms()
    assert h["serve.resolve"]["count"] == 9          # one per token
    assert h["serve.prefill"]["count"] == 2          # one per generate
    assert h["serve.first_token"]["count"] == 2
    assert h["serve.rebuild"]["count"] == 1


def test_train_deploy_keeps_one_rebuild_record_and_rollback_none():
    state, bindings, step, loop = trainer()
    state = loop.run(state, 1)
    bindings["train_loss"].deploy(LOSS.format(scale=2.0))
    state = loop.run(state, 1)
    dep = bindings["train_loss"].deploy(LOSS.format(scale=3.0))
    state = loop.run(state, 2)
    recs = records(step.spans, "train.rebuild")
    assert len(recs) == 3              # builtin, then one per deploy
    new = recs[-1]
    assert new["md5s"]["train_loss"] == dep.md5
    assert all(new[k] > 0 for k in COMPILE_EVENTS.values())
    dep.rollback()                     # to a version already compiled
    loop.run(state, 2)
    assert len(records(step.spans, "train.rebuild")) == 3
    assert step.rebuilds == 3 and step.swap_events == 3
    assert step.metrics.counter("train.rebuilds") == 3


def test_serve_deploy_keeps_one_rebuild_record_and_rollback_none():
    engine, binding, params, prompt = server()
    v1 = engine.deploy_sampler(SAMPLER.format(shift=1.0))
    engine.generate(params, prompt, 3)
    v2 = engine.deploy_sampler(SAMPLER.format(shift=2.0))
    engine.generate(params, prompt, 3)
    recs = records(engine.spans, "serve.rebuild")
    assert [r["md5s"]["sampler"] for r in recs] == [v1.md5, v2.md5]
    assert all(recs[-1][k] > 0 for k in COMPILE_EVENTS.values())
    v2.rollback()                      # to a version already compiled
    engine.generate(params, prompt, 3)
    assert len(records(engine.spans, "serve.rebuild")) == 2
    assert engine.rebuilds == 2


def test_background_compile_is_recorded_on_its_thread():
    state, bindings, step, loop = trainer(async_compile=True)
    state = loop.run(state, 1)
    dep = bindings["train_loss"].deploy(LOSS.format(scale=2.0))
    deadline = time.time() + 120
    while time.time() < deadline:
        state = loop.run(state, 1)
        if loop.history[-1]["code_md5"]["train_loss"] == dep.md5:
            break
    new = records(step.spans, "train.rebuild")[-1]
    assert new["md5s"]["train_loss"] == dep.md5
    assert new["lower_s"] > 0 and new["backend_s"] > 0
    assert step.stall_free_steps >= 1
    assert step.metrics.counter("train.stall_free_steps") == \
        step.stall_free_steps


def test_counters_are_read_only():
    _, _, step, _ = trainer()
    engine, _, _, _ = server()
    for obj, name in ((step, "rebuilds"), (step, "swap_events"),
                      (step, "stall_free_steps"), (engine, "rebuilds")):
        assert getattr(obj, name) == 0
        with pytest.raises(AttributeError):
            setattr(obj, name, 1)


def test_lowered_steps_carry_the_scope_names():
    state, _, step, loop = trainer()
    batch = batch_at(loop.task, 0)
    text = step._build({s: None for s in step.SLOTS}).lower(
        state, batch).as_text(debug_info=True)
    for scope in ("attention", "mlp", "unembed", "loss", "optimizer"):
        assert has_scope(text, scope), scope
    engine, _, params, prompt = server()
    engine.generate(params, prompt, 2)
    fp = next(iter(engine._cache))
    logits, cache, pos = engine.prefill(params, prompt)
    text = engine._cache[fp].lower(
        params, jnp.zeros((2,), jnp.int32), cache, pos,
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    for scope in ("attention", "mlp", "unembed", "sampler"):
        assert has_scope(text, scope), scope
    assert "jit(prefill)" in engine._prefill_jit.lower(
        params, prompt, cache).as_text(debug_info=True)


# ---------------------------------------------------------------------------

def test_timed_observes_milliseconds_only_when_the_body_returns():
    m = Metrics()
    with timed(m, "x.work"):
        time.sleep(0.01)
    with pytest.raises(ValueError):
        with timed(m, "x.work"):
            raise ValueError
    h = m.histograms()["x.work"]
    assert h["count"] == 1 and 9 <= h["sum"] < 1000


def test_a_jit_traced_inside_another_counts_once():
    inner = jax.jit(lambda x: jnp.cos(x) + 1)
    outer = jax.jit(lambda x: inner(x) * inner(x + 1) + 0.5)
    spans = SpanRecorder("t")
    before = compile_listener().totals()
    with rebuild_span(Metrics(), spans, "t.rebuild", {"slot": "m"}):
        t0 = time.perf_counter()
        outer(jnp.ones(5)).block_until_ready()
        wall = time.perf_counter() - t0
    after = compile_listener().totals()
    rec = spans.drain()[0]["attrs"]
    assert rec["md5s"] == {"slot": "m"}
    # the totals hold the inner trace twice, inside and beside the outer
    assert 0 < rec["trace_s"] < after["trace_s"] - before["trace_s"]
    assert rec["backend_s"] == pytest.approx(
        after["backend_s"] - before["backend_s"])
    assert rec["trace_s"] + rec["lower_s"] + rec["backend_s"] <= wall


def test_compiles_on_another_thread_stay_out_of_the_record():
    spans = SpanRecorder("t")
    before = compile_listener().totals()
    other = threading.Thread(target=lambda: jax.jit(
        lambda x: jnp.tan(x) - 3)(jnp.ones(3)).block_until_ready())
    with rebuild_span(Metrics(), spans, "t.rebuild", {"slot": "m"}):
        other.start()
        other.join()
    assert compile_listener().totals()["backend_s"] > before["backend_s"]
    rec = spans.drain()[0]["attrs"]
    assert all(rec[k] == 0 for k in COMPILE_EVENTS.values())
