"""A whole run on the CPU at a tiny size, past the harness's look for a
chip, with the timed path broken underneath: ``correct`` comes out
false for each fault a cell can have, and true without one."""
import jax
import jax.numpy as jnp
import pytest

from bench import harness
from repro.serve import engine as serve_engine
from repro.train.step import HotSwapTrainStep

from tiny import tiny_cell

SEED = 2 ** 31 + 101


def run(cell_name, seconds=1.0, cell=None):
    cell = cell or tiny_cell(cell_name)
    return harness.run_cell(cell_name, SEED, seconds, False, allow_cpu=True,
                            cell=cell, log=lambda *a, **k: None)


def state_unchanged(monkeypatch):
    step = HotSwapTrainStep.__call__

    def stuck(self, state, batch):
        _, metrics = step(self, jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    monkeypatch.setattr(HotSwapTrainStep, "__call__", stuck)


def half_batch(monkeypatch):
    step = HotSwapTrainStep.__call__

    def half(self, state, batch):
        return step(self, state, {k: v[: v.shape[0] // 2]
                                  for k, v in batch.items()})
    monkeypatch.setattr(HotSwapTrainStep, "__call__", half)


def stale_loss(monkeypatch):
    """Every step program keeps the first ``train_loss`` it was built
    with, while the md5s still name the module deployed last."""
    build = HotSwapTrainStep._build
    first = {}

    def stale(self, fns):
        first.setdefault("fn", fns["train_loss"])
        return build(self, dict(fns, train_loss=first["fn"]))
    monkeypatch.setattr(HotSwapTrainStep, "_build", stale)


def token_altered(monkeypatch):
    make = serve_engine.make_serve_step

    def altered(model, ctx, sampler):
        inner = make(model, ctx, sampler)

        def step(*args):
            nxt, cache, pos, key = inner(*args)
            return (nxt + 1) % model.cfg.vocab_size, cache, pos, key
        return step
    monkeypatch.setattr(serve_engine, "make_serve_step", altered)


TRAIN = ["smollm-train-swap", "smollm-train-steady"]
SERVE = ["qwen3-serve-swap", "qwen3-serve-steady"]


@pytest.mark.parametrize("cell", TRAIN + SERVE)
def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
@pytest.mark.parametrize("cell", TRAIN)
def test_training_fault_is_caught(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = run(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", SERVE)
def test_altered_token_is_caught(cell, monkeypatch):
    token_altered(monkeypatch)
    out = run(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("stale", [False, True])
def test_window_deploy_is_what_the_check_compares(stale, monkeypatch):
    """The swap cell's check follows the module the window deployed last:
    with coefficients large enough to tell apart, a step program that
    kept the set-up's module fails, and the sound one passes."""
    cell = tiny_cell("smollm-train-swap")
    cell.traffic["deploys"]["z_coef_range"] = [0.05, 0.1]
    if stale:
        stale_loss(monkeypatch)
    out = run(cell.name, cell=cell)
    assert out["correct"] is not stale, out["checks"]
