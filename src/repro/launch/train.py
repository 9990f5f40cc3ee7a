"""Training driver: one process, one device, the hot-swap train step.

Builds the run config, the model, the optimizer state, the slot
bindings and the ``TrainLoop``, with checkpoint/restore and a save on
preemption. ``--reduced`` shrinks the model to a CPU-sized config;
``--seq``/``--batch`` override the shape's sequence length and global
batch on their own, so a full-width model can run at a size one chip
holds. (The sharded multi-pod lowering is exercised by ``dryrun.py``.)

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 200 --reduced --seq 128 --batch 8 --ckpt /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Any, Dict, NamedTuple, Optional

import jax

from repro.checkpoint.store import CheckpointStore
from repro.configs import RunConfig, make_run_config
from repro.core.registry import ActiveCodeRegistry, Binding
from repro.data.synthetic import make_task
from repro.launch.cache import setup_compile_cache
from repro.models import build_model
from repro.optim.api import build_optimizer
from repro.train import HotSwapTrainStep, TrainLoop, TrainState, init_state


def build_run(arch: str, shape: str = "train_4k", *, reduced: bool = False,
              seq: Optional[int] = None, batch: Optional[int] = None,
              lr: Optional[float] = None,
              steps: Optional[int] = None) -> RunConfig:
    """The run config for ``arch`` at ``shape``. ``reduced`` shrinks the
    model only; ``seq``/``batch``/``lr`` override the shape and the
    learning rate when given. ``steps`` sets the schedule's length, with
    warmup cut to at most a tenth of it."""
    run = make_run_config(arch, shape)
    train = run.train
    if lr is not None:
        train = dataclasses.replace(train, learning_rate=lr)
    if steps is not None:
        train = dataclasses.replace(
            train, total_steps=steps,
            warmup_steps=min(train.warmup_steps, max(1, steps // 10)))
    return dataclasses.replace(
        run,
        model=run.model.reduced() if reduced else run.model,
        shape=dataclasses.replace(
            run.shape, seq_len=seq or run.shape.seq_len,
            global_batch=batch or run.shape.global_batch),
        train=train)


class Trainer(NamedTuple):
    state: TrainState
    bindings: Dict[str, Binding]
    step: HotSwapTrainStep
    loop: TrainLoop


def build_trainer(run: RunConfig, *, user: str = "analyst",
                  store: Optional[CheckpointStore] = None,
                  ckpt_every: int = 0) -> Trainer:
    """Model, optimizer state (initialised in one jitted program), one
    registry binding per slot, the hot-swap step and its loop."""
    model = build_model(run.model)
    opt = build_optimizer(run.train, run.model.param_dtype)
    state = jax.jit(lambda key: init_state(model, opt, key, run))(
        jax.random.PRNGKey(run.train.seed))
    reg = ActiveCodeRegistry()
    bindings = {s: reg.bind(user, s) for s in HotSwapTrainStep.SLOTS}
    step = HotSwapTrainStep(model, run, opt, bindings)
    task = make_task(run.model.vocab_size, run.shape.seq_len,
                     run.shape.global_batch, seed=run.train.seed)
    loop = TrainLoop(step, task, run, store=store, ckpt_every=ckpt_every)
    return Trainer(state, bindings, step, loop)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true",
                    help="shrink the model to its CPU-sized config")
    ap.add_argument("--seq", type=int, default=None,
                    help="sequence length (default: the shape's)")
    ap.add_argument("--batch", type=int, default=None,
                    help="global batch (default: the shape's)")
    ap.add_argument("--lr", type=float, default=None,
                    help="peak learning rate (default: the config's)")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-json", default="")
    args = ap.parse_args()

    setup_compile_cache()
    run = build_run(args.arch, args.shape, reduced=args.reduced,
                    seq=args.seq, batch=args.batch, lr=args.lr,
                    steps=args.steps)
    store = CheckpointStore(args.ckpt) if args.ckpt else None
    t = build_trainer(run, user=os.environ.get("USER", "analyst"),
                      store=store,
                      ckpt_every=args.ckpt_every if store else 0)
    state = t.state
    if args.resume and store and store.latest():
        state, at = store.restore_latest(state)
        print(f"resumed from step {at}")
    t.loop.install_sigterm_save()

    def on_step(i: int, m: Dict[str, Any]) -> None:
        if i % 10 == 0:
            print(f"step {i:5d} loss {m['loss']:.4f} acc "
                  f"{m.get('accuracy', 0):.3f} {m['step_ms']:.0f}ms",
                  flush=True)

    t0 = time.time()
    state = t.loop.run(state, args.steps, on_step=on_step)
    print(f"done {args.steps} steps in {time.time() - t0:.1f}s; "
          f"final loss {t.loop.history[-1]['loss']:.4f}")
    if args.log_json:
        with open(args.log_json, "w") as f:
            json.dump(t.loop.history, f, indent=1, default=str)


if __name__ == "__main__":
    main()
