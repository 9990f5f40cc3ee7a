"""End-to-end training driver: a ~135M-class arch (reduced for CPU) on
the synthetic LM task for a few hundred steps, with an active-code loss
swap and a checkpoint/restore cycle mid-run.

    PYTHONPATH=src python examples/train_hotswap.py [--steps 300]

(The full-width smollm-135m runs the same code path on one TPU via
``python -m repro.launch.train --arch smollm-135m --seq 1024 --batch 8``
and ``chip_smoke.py``; the dry-run proves the sharded lowering.)
"""
import argparse
import dataclasses
import sys
import tempfile

sys.path.insert(0, "src")

import jax

from repro.checkpoint.store import CheckpointStore
from repro.configs import make_run_config
from repro.core.registry import ActiveCodeRegistry
from repro.data.synthetic import make_task
from repro.models import build_model
from repro.optim.api import build_optimizer
from repro.train import HotSwapTrainStep, TrainLoop, init_state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    args = ap.parse_args()

    run = make_run_config("smollm-135m", "train_4k")
    run = dataclasses.replace(
        run,
        model=run.model.reduced(num_layers=4, d_model=128),
        shape=dataclasses.replace(run.shape, seq_len=128, global_batch=16),
        train=dataclasses.replace(run.train, learning_rate=5e-3,
                                  warmup_steps=20,
                                  total_steps=args.steps))
    model = build_model(run.model)
    opt = build_optimizer(run.train, run.model.param_dtype)
    state = init_state(model, opt, jax.random.PRNGKey(0), run)

    reg = ActiveCodeRegistry()
    bindings = {s: reg.bind("analyst", s) for s in HotSwapTrainStep.SLOTS}
    step = HotSwapTrainStep(model, run, opt, bindings)
    task = make_task(run.model.vocab_size, run.shape.seq_len,
                     run.shape.global_batch, seed=0)
    ckpt_dir = tempfile.mkdtemp(prefix="ckpt-")
    store = CheckpointStore(ckpt_dir)
    loop = TrainLoop(step, task, run, store=store, ckpt_every=100)

    def log(i, m):
        if i % 20 == 0:
            tag = m["code_md5"]["train_loss"][:8]
            print(f"step {i:4d}  loss {m['loss']:.4f}  acc "
                  f"{m.get('accuracy', 0):.3f}  loss-code {tag}",
                  flush=True)

    third = args.steps // 3
    print(f"== phase 1: builtin cross-entropy ({third} steps)")
    state = loop.run(state, third, on_step=log)

    print("== phase 2: hot-swap z-loss-regularized CE (no restart)")
    deploy = bindings["train_loss"].deploy("""
import jax, jax.numpy as jnp
def run(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)
    return jnp.mean(logz - gold.squeeze(-1)) + 1e-4 * jnp.mean(logz ** 2)
""")
    print(f"   deployed train_loss v{deploy.version} ({deploy.md5[:8]}); "
          f"a later deploy could rollback() to this version instantly")
    state = loop.run(state, third, on_step=log)

    print("== phase 3: simulate preemption -> restore -> continue")
    store.save(state, step=int(state.step))
    state2, at = store.restore_latest(state)
    print(f"   restored at step {at} (bit-exact resume; data pipeline is "
          f"stateless in (seed, step))")
    state2 = loop.run(state2, args.steps - 2 * third, on_step=log)

    l0, l1 = loop.history[0]["loss"], loop.history[-1]["loss"]
    print(f"\nfinal: loss {l0:.3f} -> {l1:.3f}  "
          f"acc {loop.history[-1].get('accuracy', 0):.3f}  "
          f"(swaps={step.swap_events}, re-jits={step.rebuilds})")
    assert l1 < l0 * 0.5, "training must learn the synthetic task"


if __name__ == "__main__":
    main()
