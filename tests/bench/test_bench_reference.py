"""The plain reference against the program at reduced widths, in
float32 on both sides, so that the equations and not the rounding are
compared."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.drivers import train as train_driver
from bench.reference import dense_lm

from tiny import tiny_cell


def f32(cell):
    cell.config["dtypes"]["compute"] = "float32"
    cell.config["overrides"]["dtype"] = "float32"
    return cell


@pytest.mark.parametrize("z_coef", [None, 3e-3])
def test_training_steps_match_reference(z_coef):
    cell = f32(tiny_cell("smollm-train-swap"))
    cell.traffic["setup_z_coef"] = z_coef
    drv = train_driver.Driver(cell, 2 ** 31 + 5, harness.CompileLog())
    drv.setup()
    if z_coef is not None:
        md5 = drv.trainer.loop.history[0]["code_md5"]["train_loss"]
        assert md5 != "builtin"           # the steps ran the deployed module
    got = train_driver.compare(drv.readings(), drv.reference(), cell.limits)
    assert got["loss_rel"][0] < 2e-6
    assert got["grad_rel"][0] < 2e-5
    assert got["change_rel"][0] < 2e-3


def test_reference_sees_a_changed_loss():
    cell = f32(tiny_cell("smollm-train-swap"))
    cell.traffic["setup_z_coef"] = 0.05
    drv = train_driver.Driver(cell, 7, harness.CompileLog())
    drv.setup()
    drv.z_coef = 0.0                      # the reference without the z-loss
    got = train_driver.compare(drv.readings(), drv.reference(), cell.limits)
    assert got["loss_rel"][0] > 1e-3


def test_prefill_and_decode_logits_match_reference():
    from repro.launch import serve
    cell = f32(tiny_cell("qwen3-serve-steady"))
    m = cell.m
    run = serve.build_run(cell.config["preset"], batch=2, max_seq=24)
    run = run.replace(model=harness.program_model(cell.config))
    engine, _ = serve.build_server(run)
    params = weights.make_params(3, m)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, m["vocab"], (2, 12)).astype(np.int32)
    logits, cache, pos = engine.prefill(params, jnp.asarray(prompt))
    toks = [jnp.argmax(logits, -1).astype(jnp.int32)]
    got = [logits]
    for _ in range(5):
        logits, cache = engine.model.decode_step(params, toks[-1], cache, pos,
                                                 engine.ctx)
        pos = pos + 1
        got.append(logits)
        toks.append(jnp.argmax(logits, -1).astype(jnp.int32))
    seq = np.concatenate([prompt, np.stack(toks[:-1], 1)], axis=1)
    for b in range(2):
        ref = dense_lm.logits(params, jnp.asarray(seq[b]), m,
                              rows=np.arange(11, 17))
        prog = np.stack([np.asarray(g[b]) for g in got])
        np.testing.assert_allclose(prog, np.asarray(ref), atol=2e-4, rtol=0)
    gaps = dense_lm.served_gaps(params, prompt[0],
                                np.asarray([t[0] for t in toks]), m)
    assert gaps.max() < 1e-4              # greedy tokens are the best ones
