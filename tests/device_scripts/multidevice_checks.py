"""Multi-device correctness checks, run as a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests/conftest keeps
the main pytest process at 1 device, as a one-chip run does).

Exits 0 iff every check passes; prints one line per check.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses

import jax
import jax.numpy as jnp
import jax.random as jr
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))

from repro.configs import get_config, make_run_config
from repro.models import build_model, moe
from repro.models.blocks import ModelCtx
from repro.optim.compression import compressed_psum
from repro.sharding.auto import run_rules, shardings_for
from repro.launch.mesh import make_mesh_like
from repro.launch.specs import param_shardings

FAILURES = []


def check(name, ok):
    print(("PASS" if ok else "FAIL"), name, flush=True)
    if not ok:
        FAILURES.append(name)


def moe_ep_multidevice():
    cfg = dataclasses.replace(get_config("dbrx-132b").reduced(),
                              capacity_factor=8.0)
    mesh = make_mesh_like((2, 4), ("data", "model"))
    p = moe.moe_init(jr.PRNGKey(0), cfg, jnp.float32)
    x = jr.normal(jr.PRNGKey(1), (4, 16, cfg.d_model))
    y_d, _ = jax.jit(lambda p, x: moe.moe_apply_dense(p, x, cfg))(p, x)
    with jax.set_mesh(mesh):
        y_e, _ = jax.jit(lambda p, x: moe.moe_apply_ep(p, x, cfg, mesh))(p, x)
    check("moe_ep_8dev_fwd", float(jnp.abs(y_e - y_d).max()) < 1e-5)

    def loss(p, x):
        y, aux = moe.moe_apply_ep(p, x, cfg, mesh)
        return (y ** 2).mean() + 0.01 * aux

    with jax.set_mesh(mesh):
        g = jax.jit(jax.grad(loss))(p, x)
    ok = all(bool(jnp.isfinite(v).all()) for v in jax.tree.leaves(g))
    check("moe_ep_8dev_grad_finite", ok)


def seqshard_decode_multidevice():
    for name in ("qwen3-0.6b", "hymba-1.5b"):
        cfg = get_config(name).reduced()
        m = build_model(cfg)
        p = m.init(jr.PRNGKey(0))
        mesh = make_mesh_like((2, 4), ("data", "model"))
        ctx_d = ModelCtx(attn_impl="blockwise", decode_attn_impl="ref",
                         moe_impl="dense", remat_policy="none")
        ctx_s = ModelCtx(mesh=mesh, attn_impl="blockwise",
                         decode_attn_impl="seqshard", moe_impl="dense",
                         remat_policy="none", tp_axis="model")
        toks = jr.randint(jr.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
        cache = m.init_cache(2, 64, ctx_d)
        lg, cache1, pos = jax.jit(
            lambda p, t, c: m.prefill(p, t, c, ctx_d))(p, toks, cache)
        t0 = jnp.argmax(lg, -1).astype(jnp.int32)
        lg_d, _ = jax.jit(lambda p, t, c, q: m.decode_step(
            p, t, c, q, ctx_d))(p, t0, cache1, pos)
        kv = NamedSharding(mesh, P(None, None, None, "model", None))
        cache_s = jax.tree.map(
            lambda a: jax.device_put(a, kv)
            if a.ndim == 5 and a.shape[3] >= 8 else a, cache1)
        with jax.set_mesh(mesh):
            lg_s, _ = jax.jit(lambda p, t, c, q: m.decode_step(
                p, t, c, q, ctx_s))(p, t0, cache_s, pos)
        check(f"seqshard_decode_{name}",
              float(jnp.abs(lg_s - lg_d).max()) < 5e-5)


def compressed_psum_multidevice():
    mesh = make_mesh_like((8,), ("data",))
    g = {"w": jr.normal(jr.PRNGKey(2), (8, 64))}
    gs = jax.device_put(g, NamedSharding(mesh, P("data", None)))
    with jax.set_mesh(mesh):
        out = jax.jit(
            lambda t: compressed_psum(t, mesh, ("data",),
                                      spec_fn=lambda l: P("data", None))
        )(gs)
    # each rank held one row; psum-mean across ranks => row-mean bcast
    want = np.asarray(g["w"]).mean(axis=0)
    got = np.asarray(out["w"][0])
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    check("compressed_psum_int8", rel < 0.02)   # int8 quantization error


def sharded_train_step_multidevice():
    """The full HotSwap train step under pjit on a 4x2 mesh, vs the
    single-device result: losses must match closely."""
    from repro.optim.api import build_optimizer
    from repro.train import HotSwapTrainStep, init_state
    from repro.core.registry import ActiveCodeRegistry
    from repro.data.synthetic import batch_at, make_task
    from repro.launch.specs import abstract_state, state_shardings

    run = make_run_config("smollm-135m", "train_4k")
    run = dataclasses.replace(
        run, model=run.model.reduced(),
        shape=dataclasses.replace(run.shape, seq_len=64, global_batch=8),
        train=dataclasses.replace(run.train, learning_rate=1e-3,
                                  warmup_steps=2, total_steps=20))
    task = make_task(run.model.vocab_size, 64, 8, seed=0)

    losses = {}
    for tag, mesh in (("1dev", None),
                      ("4x2", make_mesh_like((4, 2), ("data", "model")))):
        model = build_model(run.model)
        opt = build_optimizer(run.train, run.model.param_dtype)
        state = init_state(model, opt, jr.PRNGKey(0), run)
        reg = ActiveCodeRegistry()
        bindings = {s: reg.bind("u", s) for s in
                    ("train_loss", "train_metrics", "grad_transform")}
        if mesh is None:
            step = HotSwapTrainStep(model, run, opt, bindings)
            ls = []
            for i in range(3):
                state, m = step(state, batch_at(task, i))
                ls.append(float(m["loss"]))
        else:
            rules = run_rules(run)
            with jax.set_mesh(mesh):
                step = HotSwapTrainStep(model, run, opt, bindings,
                                        mesh=mesh, rules=rules)
                ls = []
                for i in range(3):
                    state, m = step(state, batch_at(task, i))
                    ls.append(float(m["loss"]))
        losses[tag] = ls
    diff = max(abs(a - b) for a, b in zip(losses["1dev"], losses["4x2"]))
    check("sharded_train_step_matches", diff < 1e-3)


def elastic_reshard_roundtrip():
    """Checkpoint written unsharded, restored onto a 2x4 mesh with
    param shardings (elastic reshard-on-load)."""
    import tempfile
    from repro.checkpoint.store import restore_tree, save_tree

    cfg = get_config("qwen3-0.6b").reduced()
    model = build_model(cfg)
    p = model.init(jr.PRNGKey(0))
    with tempfile.TemporaryDirectory() as td:
        path = save_tree(td, p, step=1)
        mesh = make_mesh_like((2, 4), ("data", "model"))
        run = make_run_config("qwen3-0.6b", "train_4k")
        run = dataclasses.replace(run, model=cfg)
        rules = run_rules(run)
        p_sds = jax.eval_shape(model.init,
                               jax.ShapeDtypeStruct((2,), jnp.uint32))
        shd = param_shardings(model, p_sds, rules, mesh)
        got = restore_tree(path, p, shardings=shd)
    same = all(np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
               for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(got)))
    sharded = any(len(x.sharding.device_set) > 1
                  for x in jax.tree.leaves(got))
    check("elastic_reshard_values", same)
    check("elastic_reshard_sharded", sharded)


if __name__ == "__main__":
    assert jax.device_count() == 8, jax.device_count()
    moe_ep_multidevice()
    seqshard_decode_multidevice()
    compressed_psum_multidevice()
    sharded_train_step_multidevice()
    elastic_reshard_roundtrip()
    print("FAILURES:", FAILURES, flush=True)
    sys.exit(1 if FAILURES else 0)
