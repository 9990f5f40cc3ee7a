"""Training-layer tests: convergence, hot-swap semantics, microbatch
equivalence, compression, md5-tagged metrics."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import make_run_config
from repro.core.registry import ActiveCodeRegistry
from repro.data.synthetic import batch_at, make_task
from repro.models import build_model
from repro.optim.api import build_optimizer
from repro.train import HotSwapTrainStep, TrainLoop, init_state


def small_run(arch="smollm-135m", **train_kw):
    run = make_run_config(arch, "train_4k")
    kw = dict(learning_rate=1e-2, warmup_steps=5, total_steps=100,
              num_microbatches=1)
    kw.update(train_kw)
    return dataclasses.replace(
        run, model=run.model.reduced(),
        shape=dataclasses.replace(run.shape, seq_len=64, global_batch=8),
        train=dataclasses.replace(run.train, **kw))


def build(run, user="u"):
    model = build_model(run.model)
    opt = build_optimizer(run.train, run.model.param_dtype)
    state = init_state(model, opt, jax.random.PRNGKey(0), run)
    reg = ActiveCodeRegistry()
    bindings = {s: reg.bind(user, s)
                for s in ("train_loss", "train_metrics", "grad_transform")}
    step = HotSwapTrainStep(model, run, opt, bindings)
    task = make_task(run.model.vocab_size, run.shape.seq_len,
                     run.shape.global_batch, seed=0)
    return model, opt, state, reg, step, task


def test_loss_decreases():
    run = small_run()
    _, _, state, _, step, task = build(run)
    loop = TrainLoop(step, task, run)
    state = loop.run(state, 40)
    assert loop.history[-1]["loss"] < loop.history[0]["loss"] * 0.5
    assert loop.history[-1]["accuracy"] > 0.5


def test_hot_swap_loss_mid_run():
    run = small_run()
    _, _, state, reg, step, task = build(run)
    loop = TrainLoop(step, task, run)
    state = loop.run(state, 5)
    assert loop.history[-1]["code_md5"]["train_loss"] == "builtin"

    mod = reg.deploy("u", "train_loss", """
import jax, jax.numpy as jnp
def run(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)
    return jnp.mean(logz - gold.squeeze(-1)) + 1e-4 * jnp.mean(logz ** 2)
""")
    state = loop.run(state, 5)
    assert loop.history[-1]["code_md5"]["train_loss"] == mod.md5
    assert step.swap_events == 1
    assert step.rebuilds == 2
    # training continued: same state thread, step counter advanced
    assert int(state.step) == 10


def test_swap_back_hits_jit_cache():
    """A/B flip-flop: returning to an already-seen version re-jits
    nothing (improvement over the paper's reload-per-iteration)."""
    run = small_run()
    _, _, state, reg, step, task = build(run)
    loop = TrainLoop(step, task, run)
    state = loop.run(state, 2)
    m1 = reg.deploy("u", "train_loss",
                    "import jax\nimport jax.numpy as jnp\n"
                    "def run(l, y):\n"
                    "    lz = jax.nn.logsumexp(l, -1)\n"
                    "    g = jnp.take_along_axis(l, y[..., None], -1)\n"
                    "    return jnp.mean(lz - g.squeeze(-1))\n")
    state = loop.run(state, 2)
    reg.rollback("u", "train_loss", m1.md5)      # same version again
    state = loop.run(state, 2)
    assert step.rebuilds == 2                    # builtin + m1, no third


def test_metrics_slot_swap():
    run = small_run()
    _, _, state, reg, step, task = build(run)
    loop = TrainLoop(step, task, run)
    state = loop.run(state, 2)
    assert "top5" not in loop.history[-1]
    reg.deploy("u", "train_metrics", """
import jax, jax.numpy as jnp
def run(logits, labels):
    top5 = jax.lax.top_k(logits, 5)[1]
    hit = (top5 == labels[..., None]).any(-1)
    return {"top5": jnp.mean(hit.astype(jnp.float32))}
""")
    state = loop.run(state, 2)
    assert "top5" in loop.history[-1]


def test_bad_deploy_rejected_training_unaffected():
    run = small_run()
    _, _, state, reg, step, task = build(run)
    loop = TrainLoop(step, task, run)
    state = loop.run(state, 3)
    from repro.core.validation import ValidationError
    with pytest.raises(ValidationError):
        reg.deploy("u", "train_loss", "import os\ndef run(l, y): ...")
    state = loop.run(state, 3)
    assert loop.history[-1]["code_md5"]["train_loss"] == "builtin"
    assert step.swap_events == 0


def test_microbatch_equivalence():
    """M=1 and M=2 produce (nearly) identical updates in fp32."""
    losses = {}
    for M in (1, 2):
        run = small_run(num_microbatches=M)
        _, _, state, _, step, task = build(run)
        loop = TrainLoop(step, task, run)
        state = loop.run(state, 5)
        losses[M] = [h["loss"] for h in loop.history]
    np.testing.assert_allclose(losses[1], losses[2], rtol=2e-3, atol=2e-3)


def test_grad_compression_int8_trains():
    run = small_run(grad_compression="int8_ef", learning_rate=5e-3)
    _, _, state, _, step, task = build(run)
    assert state.comp_state != ()
    loop = TrainLoop(step, task, run)
    state = loop.run(state, 40)
    assert loop.history[-1]["loss"] < loop.history[0]["loss"] * 0.7


def test_grad_transform_slot_swap():
    """Swap the compression strategy mid-run (the paper's A/B case at
    the distributed-optimization layer)."""
    run = small_run()
    _, _, state, reg, step, task = build(run)
    # grad_transform slot: signature (grads, comp_state) -> same
    loop = TrainLoop(step, task, run)
    state = loop.run(state, 3)
    reg.deploy("u", "grad_transform", """
import jax, jax.numpy as jnp
def run(grads, state):
    # crude sign-SGD-style transform
    return jax.tree.map(lambda g: jnp.sign(g) * 1e-3, grads), state
""")
    state = loop.run(state, 3)
    assert step.swap_events == 1
    assert bool(jnp.isfinite(
        jnp.asarray(loop.history[-1]["loss"])))


def test_data_determinism_across_restart():
    task = make_task(256, 32, 4, seed=7)
    b1 = batch_at(task, 123)
    b2 = batch_at(task, 123)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = batch_at(task, 124)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))


def test_async_zero_stall_swap():
    """Deploy with async_compile: steps keep running the old version
    (correctly md5-tagged) until the background compile finishes, then
    cut over; no step ever blocks on the new compile."""
    import time
    run = small_run()
    model = build_model(run.model)
    opt = build_optimizer(run.train, run.model.param_dtype)
    state = init_state(model, opt, jax.random.PRNGKey(0), run)
    reg = ActiveCodeRegistry()
    bindings = {s: reg.bind("u", s) for s in HotSwapTrainStep.SLOTS}
    step = HotSwapTrainStep(model, run, opt, bindings, async_compile=True)
    for i in range(3):
        state, m = step(state, batch_at(run_task(run), i))
    mod = reg.deploy("u", "train_loss", """
import jax, jax.numpy as jnp
def run(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)
    return jnp.mean(logz - gold.squeeze(-1)) * 2.0
""")
    seen = []
    deadline = time.time() + 120
    i = 3
    while time.time() < deadline:
        state, m = step(state, batch_at(run_task(run), i))
        seen.append(m["code_md5"]["train_loss"])
        i += 1
        if seen[-1] == mod.md5:
            break
    assert seen[0] == "builtin"          # old version kept running
    assert seen[-1] == mod.md5           # eventually cut over
    assert step.stall_free_steps >= 1


def test_async_compile_error_raised_at_cutover():
    """A background compile that fails is not swallowed: the step that
    would cut over to the new version raises the compile's error."""
    run = small_run()
    model = build_model(run.model)
    opt = build_optimizer(run.train, run.model.param_dtype)
    state = init_state(model, opt, jax.random.PRNGKey(0), run)
    reg = ActiveCodeRegistry()
    bindings = {s: reg.bind("u", s) for s in HotSwapTrainStep.SLOTS}
    step = HotSwapTrainStep(model, run, opt, bindings, async_compile=True)
    state, _ = step(state, batch_at(run_task(run), 0))
    # a per-token loss: passes validation, but value_and_grad refuses a
    # non-scalar output when the new step is traced
    reg.deploy("u", "train_loss", """
import jax, jax.numpy as jnp
def run(logits, labels):
    return jax.nn.logsumexp(logits, axis=-1)
""")
    deadline = time.time() + 120
    with pytest.raises(TypeError, match="scalar"):
        i = 1
        while time.time() < deadline:
            state, _ = step(state, batch_at(run_task(run), i))
            i += 1
    assert step.rebuilds == 1            # the failed version never ran


def run_task(run):
    return make_task(run.model.vocab_size, run.shape.seq_len,
                     run.shape.global_batch, seed=0)
