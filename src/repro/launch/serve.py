"""Serving driver: batched generation with a hot-swappable sampler,
swapped mid-generation through the versioned deployment API (deploy ->
generate -> rollback).

``--reduced`` shrinks the model to a CPU-sized config; ``--batch`` and
``--max-seq`` (the KV cache length) override the shape on their own.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --reduced --max-seq 256 --tokens 32
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs import RunConfig, make_run_config
from repro.core.registry import ActiveCodeRegistry
from repro.launch.cache import setup_compile_cache
from repro.models import build_model
from repro.serve.engine import ServeEngine

GREEDY_SAMPLER = (
    "import jax.numpy as jnp\n"
    "def run(logits, key):\n"
    "    return jnp.argmax(logits, axis=-1).astype('int32')\n")


def temperature_sampler_source(temp: float) -> str:
    return ("import jax\n"
            "def run(logits, key):\n"
            f"    return jax.random.categorical(key, logits / {temp})"
            f".astype('int32')\n")


def build_run(arch: str, shape: str = "decode_32k", *, reduced: bool = False,
              batch: Optional[int] = None,
              max_seq: Optional[int] = None) -> RunConfig:
    """The run config for ``arch`` at ``shape``; ``reduced`` shrinks the
    model only, ``batch``/``max_seq`` override the shape when given."""
    run = make_run_config(arch, shape)
    return dataclasses.replace(
        run,
        model=run.model.reduced() if reduced else run.model,
        shape=dataclasses.replace(
            run.shape, seq_len=max_seq or run.shape.seq_len,
            global_batch=batch or run.shape.global_batch))


class Server(NamedTuple):
    engine: ServeEngine
    params: Any


def build_server(run: RunConfig, *, user: str = "analyst") -> Server:
    """The engine, with a registry binding for its sampler slot, and
    random parameters initialised in one jitted program."""
    model = build_model(run.model)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    reg = ActiveCodeRegistry()
    engine = ServeEngine(model, run,
                         sampler_binding=reg.bind(user, "sampler"))
    return Server(engine, params)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--reduced", action="store_true",
                    help="shrink the model to its CPU-sized config")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=None,
                    help="KV cache length (default: the shape's)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--swap-temp", type=float, default=0.0,
                    help="deploy a temperature sampler mid-generation "
                         "(0 = stay greedy), then roll it back")
    args = ap.parse_args()

    setup_compile_cache()
    run = build_run(args.arch, args.shape, reduced=args.reduced,
                    batch=args.batch, max_seq=args.max_seq)
    engine, params = build_server(run)

    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0,
                                run.model.vocab_size)
    frames = None
    if run.model.is_encoder_decoder:
        frames = jnp.zeros((args.batch, run.model.encoder_seq,
                            run.model.d_model), jnp.dtype(run.model.dtype))
    on_token = None
    swapped = []
    if args.swap_temp > 0:
        # v1: explicit greedy sampler, so rollback has a version to target
        engine.deploy_sampler(GREEDY_SAMPLER)
        swap_at = max(1, args.tokens // 2 - 1)

        def on_token(i, tok):
            if i == swap_at and not swapped:
                dep = engine.deploy_sampler(
                    temperature_sampler_source(args.swap_temp))
                swapped.append(dep)
                print(f"  [token {swap_at + 1}] deployed sampler "
                      f"v{dep.version} ({dep.md5[:8]}): greedy -> "
                      f"temp={args.swap_temp}")

    t0 = time.time()
    toks, info = engine.generate(params, prompt, args.tokens, frames=frames,
                                 on_token=on_token)
    toks.block_until_ready()
    dt = time.time() - t0
    print(f"generated {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.tokens / dt:.1f} tok/s); "
          f"sampler rebuilds: {info['rebuilds']}")
    print("first sequence:", toks[0, :16].tolist())
    if swapped:
        # versioned rollback: next generation is greedy again, no re-jit
        restored = swapped[-1].rollback()
        engine.generate(params, prompt, 4, frames=frames)
        print(f"rolled back to sampler v{restored.version}; "
              f"rebuilds still {engine.rebuilds}")


if __name__ == "__main__":
    main()
