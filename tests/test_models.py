"""Per-architecture smoke tests (REDUCED configs, one fwd/train step on
CPU, shape + finiteness assertions) and decode-vs-forward consistency."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, ARCH_REGISTRY, get_config
from repro.models import build_model
from repro.models import moe as moe_mod
from repro.models.blocks import ModelCtx

CTX = ModelCtx(attn_impl="blockwise", decode_attn_impl="ref",
               moe_impl="dense", remat_policy="none")
B, S = 2, 32


def _fwd(model, p, toks, frames=None):
    if model.cfg.is_encoder_decoder:
        return model.forward(p, toks, frames, CTX)
    return model.forward(p, toks, CTX)


def _setup(name):
    cfg = get_config(name).reduced()
    model = build_model(cfg)
    p = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    frames = (jnp.ones((B, cfg.encoder_seq, cfg.d_model), jnp.float32)
              if cfg.is_encoder_decoder else None)
    return cfg, model, p, toks, frames


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_forward_shapes_and_finite(name):
    cfg, model, p, toks, frames = _setup(name)
    if frames is not None:
        logits, aux = jax.jit(
            lambda p, t, f: _fwd(model, p, t, f))(p, toks, frames)
    else:
        logits, aux = jax.jit(lambda p, t: _fwd(model, p, t))(p, toks)
    assert logits.shape == (B, S, cfg.padded_vocab())
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_train_step_no_nans(name):
    """One gradient step decreases nothing NaN-ward."""
    cfg, model, p, toks, frames = _setup(name)

    def loss(p):
        logits, aux = _fwd(model, p, toks, frames)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, toks[..., None], axis=-1).squeeze(-1)
        return jnp.mean(logz - gold) + 0.01 * aux

    l, g = jax.jit(jax.value_and_grad(loss))(p)
    assert bool(jnp.isfinite(l))
    leaves = jax.tree.leaves(g)
    assert all(bool(jnp.isfinite(x).all()) for x in leaves)
    gnorm = jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2)
                         for x in leaves))
    assert float(gnorm) > 0.0


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_prefill_decode_match_forward(name):
    """Teacher-forcing consistency: prefill(t[:-1]) then one decode step
    of t[-1] must reproduce forward's last-position logits."""
    cfg, model, p, toks, frames = _setup(name)
    if cfg.n_meta_tokens:
        pytest.skip("meta-token prefix changes absolute cache layout; "
                    "covered by hymba-specific test below")
    full, _ = _fwd(model, p, toks, frames)
    cache = model.init_cache(B, S + 8, CTX)
    if cfg.is_encoder_decoder:
        lg, cache, pos = model.prefill(p, toks[:, :-1], frames, cache, CTX)
    else:
        lg, cache, pos = model.prefill(p, toks[:, :-1], cache, CTX)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, -2]),
                               atol=2e-3, rtol=2e-3)
    lg2, _ = model.decode_step(p, toks[:, -1], cache, pos, CTX)
    np.testing.assert_allclose(np.asarray(lg2), np.asarray(full[:, -1]),
                               atol=2e-3, rtol=2e-3)


def _is_axes(x):
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None)))
                                        for e in x)


def _stale(model, cache, pos, key):
    """``cache`` with seeded noise at every position >= ``pos`` on each
    leaf's position axis ("kv_seq"): what an earlier turn leaves there."""
    axes = jax.tree.leaves(model.cache_axes(), is_leaf=_is_axes)
    leaves, tree = jax.tree.flatten(cache)
    keys = jax.random.split(key, len(leaves))
    out = []
    for a, ax, k in zip(leaves, axes, keys):
        if "kv_seq" in ax and a.size:
            t = jax.lax.broadcasted_iota(jnp.int32, a.shape, ax.index("kv_seq"))
            a = jnp.where(t >= pos, jax.random.normal(k, a.shape, a.dtype), a)
        out.append(a)
    return jax.tree.unflatten(tree, out)


@pytest.mark.parametrize("stale", [False, True], ids=["zeroed", "stale"])
@pytest.mark.parametrize("name", ARCH_NAMES)
def test_decode_steps_match_forward(name, stale):
    """prefill(t[:-3]) then three decode steps of teacher-forced ids give
    forward's logits at each step; with noise in the cache at the decode
    positions and past them (stale rows of an earlier turn), every step's
    logits equal those from the zeroed cache exactly: the decode step
    reads only positions it wrote."""
    cfg, model, p, toks, frames = _setup(name)
    full, _ = _fwd(model, p, toks, frames)
    cache = model.init_cache(B, S + 8, CTX)
    if cfg.is_encoder_decoder:
        _, cache, pos = model.prefill(p, toks[:, :-3], frames, cache, CTX)
    else:
        _, cache, pos = model.prefill(p, toks[:, :-3], cache, CTX)
    step = jax.jit(lambda p, t, c, q: model.decode_step(p, t, c, q, CTX))
    noisy = _stale(model, cache, pos, jax.random.PRNGKey(7)) if stale \
        else None
    for i in range(3):
        col = S - 3 + i
        lg, cache = step(p, toks[:, col], cache, pos)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, col]),
                                   atol=2e-3, rtol=2e-3)
        if stale:
            lg_n, noisy = step(p, toks[:, col], noisy, pos)
            np.testing.assert_array_equal(np.asarray(lg_n), np.asarray(lg))
        pos = pos + 1


# the two cache layouts a decode cell serves: GQA K/V and the MLA latent
STRUCT_PRESETS = ["qwen3-0.6b", "moonlight-16b-a3b"]
SB, ST = 3, 44              # batch and positions no other array shares


def _decode_shapes(name):
    """(model, shapes of the args of a decode step, {stack shape: layer
    shape} of each cache leaf with a position axis)."""
    model = build_model(get_config(name).reduced())
    p = jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    cache = jax.eval_shape(lambda: model.init_cache(SB, ST, CTX))
    stacks = {a.shape: a.shape[1:] for a, ax in zip(
        jax.tree.leaves(cache),
        jax.tree.leaves(model.cache_axes(), is_leaf=_is_axes))
        if "kv_seq" in ax}
    args = (p, jax.ShapeDtypeStruct((SB,), jnp.int32), cache,
            jax.ShapeDtypeStruct((), jnp.int32))
    return model, args, stacks


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("name", STRUCT_PRESETS)
def test_decode_scan_reads_the_cache_in_place(name):
    """No scan of the decode step carries, slices or stacks a layer's or
    the stack's cache (the layer scan closes over it and reads it), and
    the one stack-sized result after the scan is one dynamic_update_slice
    per cache leaf."""
    model, args, stacks = _decode_shapes(name)
    sized = set(stacks) | set(stacks.values())
    jaxpr = jax.make_jaxpr(
        lambda *a: model.decode_step(*a, CTX))(*args).jaxpr
    scans = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"]
    assert scans
    for e in scans:
        moved = e.invars[e.params["num_consts"]:] + e.outvars
        assert not [v.aval.shape for v in moved
                    if getattr(v.aval, "shape", None) in sized]
    top = [e.primitive.name for e in jaxpr.eqns
           if any(v.aval.shape in stacks for v in e.outvars)]
    assert top == ["dynamic_update_slice"] * sum(
        a.shape in stacks for a in jax.tree.leaves(args[2]))


def test_donated_decode_step_copies_no_cache():
    """XLA:CPU's program of the donated serve step makes no copy of a
    cache stack and writes it in place; a stack passed through the layer
    scan as its xs and ys is copied whole after it. Qwen3 only: XLA:CPU
    copies a stack of one layer (Moonlight's leading dense layer, a scan
    of one step), which the TPU's compiler does not."""
    from repro.serve.engine import default_sampler, make_serve_step
    model, args, stacks = _decode_shapes("qwen3-0.6b")
    step = jax.jit(make_serve_step(model, CTX, default_sampler),
                   donate_argnums=(2,))
    text = step.lower(*args, jax.ShapeDtypeStruct((2,), jnp.uint32)
                      ).compile().as_text()
    shapes = {"[" + ",".join(map(str, s)) + "]"
              for kv in stacks.items() for s in kv}
    copies = [ln for ln in text.splitlines()
              if re.search(r"= \w+(\[[\d,]*\])\S* copy\(", ln)
              and re.search(r"= \w+(\[[\d,]*\])", ln).group(1) in shapes]
    assert not copies, copies
    assert "cache_write" in text


def test_hymba_prefill_decode_match_forward():
    cfg, model, p, toks, frames = _setup("hymba-1.5b")
    full, _ = model.forward(p, toks, CTX)
    cache = model.init_cache(B, S + 8, CTX)
    lg, cache, pos = model.prefill(p, toks[:, :-1], cache, CTX)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, -2]),
                               atol=2e-3, rtol=2e-3)
    lg2, _ = model.decode_step(p, toks[:, -1], cache, pos, CTX)
    np.testing.assert_allclose(np.asarray(lg2), np.asarray(full[:, -1]),
                               atol=2e-3, rtol=2e-3)


def test_param_axes_cover_params():
    """Every param leaf has a matching logical-axes leaf."""
    for name in ARCH_NAMES:
        cfg = get_config(name).reduced()
        model = build_model(cfg)
        p_sds = jax.eval_shape(model.init,
                               jax.ShapeDtypeStruct((2,), jnp.uint32))
        axes = model.param_axes()
        is_axes = lambda x: (isinstance(x, tuple)
                             and all(isinstance(e, (str, type(None)))
                                     for e in x))
        ps = jax.tree.structure(p_sds)
        ax = jax.tree.structure(axes, is_leaf=is_axes)
        assert ps == ax, f"{name}: param tree != axes tree"


def test_moe_ep_matches_dense_without_drops():
    cfg = dataclasses.replace(get_config("dbrx-132b").reduced(),
                              capacity_factor=8.0)
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y_d, _ = moe_mod.moe_apply_dense(p, x, cfg)
    y_e, _ = moe_mod.moe_apply_ep(p, x, cfg, mesh=None)
    np.testing.assert_allclose(np.asarray(y_e), np.asarray(y_d),
                               atol=1e-5, rtol=1e-5)


def test_moe_capacity_drops_bounded():
    """With capacity_factor=1.0 some tokens drop but outputs stay finite
    and the non-dropped rows match dense exactly."""
    cfg = dataclasses.replace(get_config("dbrx-132b").reduced(),
                              capacity_factor=1.0)
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.d_model))
    y_e, _ = moe_mod.moe_apply_ep(p, x, cfg, mesh=None)
    assert bool(jnp.isfinite(y_e).all())


def test_param_count_close_to_table():
    """Analytic param counts land near the published sizes."""
    expected = {
        "kimi-k2-1t-a32b": (1.0e12, 0.35),
        "dbrx-132b": (132e9, 0.15),
        "smollm-135m": (135e6, 0.15),
        "qwen3-0.6b": (0.6e9, 0.35),
        "llama3.2-3b": (3.2e9, 0.25),
        "yi-34b": (34e9, 0.15),
        "mamba2-370m": (370e6, 0.25),
        "hymba-1.5b": (1.5e9, 0.35),
    }
    for name, (want, tol) in expected.items():
        got = ARCH_REGISTRY[name].param_count()
        assert abs(got - want) / want < tol, \
            f"{name}: {got/1e9:.2f}B vs {want/1e9:.2f}B"
