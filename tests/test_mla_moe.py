"""Latent attention (MLA) and the DeepSeek-V3 MoE layer on the CPU, at
tiny sizes: the absorbed decode kernel against its XLA form, the sigmoid
router with its score-correction bias, the dropless expert path, shared
experts, the q-LoRA query of Kimi K2, and serving turns continued from a
kept cache."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, make_run_config
from repro.core.registry import ActiveCodeRegistry
from repro.kernels import ops
from repro.kernels.mla_decode import mla_decode_pallas, mla_decode_xla
from repro.models import attention as attn
from repro.models import build_model
from repro.models import moe as moe_mod
from repro.models.blocks import ModelCtx
from repro.serve.engine import ServeEngine

CTX = ModelCtx(attn_impl="blockwise", moe_impl="dropless", gmm_impl="xla",
               remat_policy="none")


def _moonlight(**kw):
    return get_config("moonlight-16b-a3b").reduced(**kw)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("B,T,pos", [(4, 1024, 0), (4, 1024, 511),
                                     (4, 1024, 512), (4, 1024, 1023),
                                     (8, 768, 700), (2, 256, 255)])
def test_latent_decode_kernel_matches_xla(B, T, pos, layer):
    """The Pallas decode kernel (interpreted) reads one layer of a stack
    of three in place, with the new row at ``pos`` (0, the last of a tile,
    the first of the next, the last of the cache), and agrees with the
    two-einsum form on that layer with the row written; the cache at
    ``pos`` and past it, and the other layers, change nothing."""
    r, rope, H, L = 64, 16, 16, 3
    ks = jax.random.split(jax.random.PRNGKey(pos), 3)
    q = jax.random.normal(ks[0], (B, H, r + rope), jnp.float32)
    c = jax.random.normal(ks[1], (L, B, r + rope, T), jnp.float32)
    row = jax.random.normal(ks[2], (B, r + rope, 1), jnp.float32)
    got = mla_decode_pallas(q, c, jnp.int32(layer), row, jnp.int32(pos),
                            r=r, scale=0.1, interpret=True)
    written = c[layer].at[:, :, pos].set(row[..., 0])
    want = mla_decode_xla(q, written[None], jnp.int32(0), row,
                          jnp.int32(pos), r=r, scale=0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(mla_decode_xla(q, c, jnp.int32(layer), row,
                                  jnp.int32(pos), r=r, scale=0.1)),
        np.asarray(want), atol=2e-5, rtol=2e-5)
    others = jnp.arange(L)[:, None, None, None] != layer
    garbage = jnp.where(others | (jnp.arange(T) >= pos), 1e4, c)
    again = mla_decode_pallas(q, garbage, jnp.int32(layer), row,
                              jnp.int32(pos), r=r, scale=0.1, interpret=True)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_absorbed_decode_equals_expanded_attention():
    """Folding W_UK into the query and W_UV into the output gives the
    attention of the expanded per-head keys and values."""
    cfg = _moonlight()
    p = attn.mla_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    S = 12
    h = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.d_model))
    full, lat = attn.mla_prefill(p, h, cfg, impl="blockwise")
    cache = attn.init_latent_cache(cfg, 2, 16, jnp.float32)
    cache = cache.at[:, :, :S - 1].set(jnp.swapaxes(lat[:, :S - 1], 1, 2))
    y, row = attn.mla_decode(p, h[:, S - 1:], cache[None], jnp.int32(0),
                             jnp.int32(S - 1), cfg)
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(full[:, -1]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(row[..., 0]),
                               np.asarray(lat[:, -1]), atol=1e-6)


def test_sigmoid_bias_chooses_but_does_not_weigh():
    """The score-correction bias moves which experts are chosen; the
    gates are the chosen experts' scores without it, renormalised and
    scaled by routed_scaling_factor."""
    cfg = _moonlight(num_experts=8, experts_per_token=2)
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    scores = jax.nn.sigmoid(x @ p["router"])
    g0, i0, _ = moe_mod._route(p, x, cfg)
    boost = p["router_bias"].at[5].set(10.0)
    g1, i1, _ = moe_mod._route(dict(p, router_bias=boost), x, cfg)
    assert bool((i1 == 5).any(axis=-1).all())           # chosen everywhere
    assert not bool((i0 == 5).any(axis=-1).all())
    for g, i in ((g0, i0), (g1, i1)):
        s = jnp.take_along_axis(scores, i, axis=-1)
        want = s / s.sum(-1, keepdims=True) * cfg.routed_scaling_factor
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   rtol=1e-5)
    # the top-2 without the bias are the two largest scores
    np.testing.assert_array_equal(
        np.sort(np.asarray(i0), -1),
        np.sort(np.asarray(jax.lax.top_k(scores, 2)[1]), -1))


def test_dropless_keeps_every_token_when_all_pick_one_expert():
    """Every token routed to expert 0 (the capacity path would drop all
    but a few): the dropless path equals the dense oracle and counts
    every token."""
    cfg = _moonlight(num_experts=8, experts_per_token=2)
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    p = dict(p, router_bias=p["router_bias"].at[0].set(100.0))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.d_model))
    y_d, _ = moe_mod.moe_apply_dense(p, x, cfg)
    y, _, load = moe_mod.moe_apply_dropless(p, x, cfg, gmm_impl="xla")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_d), atol=1e-5,
                               rtol=1e-5)
    assert int(load[0]) == 64 and int(load.sum()) == 64 * 2
    y_e, _ = moe_mod.moe_apply_ep(p, x, cfg, mesh=None)
    assert float(jnp.abs(y_e - y_d).max()) > 1e-3      # the capacity path drops


def test_shared_experts_add_to_every_token():
    cfg = _moonlight()
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, cfg.d_model))
    y, _, _ = moe_mod.moe_apply(p, x, cfg, impl="dropless", gmm_impl="xla")
    routed, _, _ = moe_mod.moe_apply_dropless(p, x, cfg, gmm_impl="xla")
    g = x @ p["shared"]["w_gate"]
    shared = (jax.nn.silu(g) * (x @ p["shared"]["w_up"])) \
        @ p["shared"]["w_down"]
    assert p["shared"]["w_gate"].shape == (cfg.d_model, 2 * cfg.moe_d_ff)
    np.testing.assert_allclose(np.asarray(y), np.asarray(routed + shared),
                               atol=1e-5, rtol=1e-5)


def test_moe_auto_is_dropless_without_a_mesh():
    """``impl="auto"``, ``ModelCtx``'s default, takes the dropless path
    where no mesh is given; a name no path has is refused."""
    cfg = _moonlight()
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, cfg.d_model))
    auto = moe_mod.moe_apply(p, x, cfg, gmm_impl="xla")
    dropless = moe_mod.moe_apply(p, x, cfg, impl="dropless", gmm_impl="xla")
    for a, b in zip(auto, dropless):
        assert jnp.array_equal(a, b)
    with pytest.raises(ValueError, match="unknown moe impl"):
        moe_mod.moe_apply(p, x, cfg, impl="ep_shardmap")


def test_leading_dense_layer_is_its_own_stack():
    cfg = _moonlight()
    model = build_model(cfg)
    p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert p["dense_layers"]["mlp"]["w_gate"].shape == (1, cfg.d_model,
                                                        cfg.d_ff)
    assert p["layers"]["moe"]["w_gate"].shape[:2] == (cfg.num_layers - 1,
                                                      cfg.num_experts)
    dense, moe = model.init_cache(2, 16, CTX)
    assert dense.latent.shape == (1, 2, cfg.latent_width(), 16)
    assert moe.latent.shape == (cfg.num_layers - 1, 2, cfg.latent_width(),
                                16)
    assert dense.kv.k.size == 0 and moe.expert_load.shape == (
        cfg.num_layers - 1, cfg.num_experts)


def test_kimi_q_lora_query_matches_its_equation():
    """Kimi K2's low-rank query: rms(h W_q_a) W_q_b, 1e-6 eps, with the
    rope part rotated in the published interleaved layout."""
    cfg = get_config("kimi-k2-1t-a32b").reduced()
    assert cfg.q_lora_rank == 24 and cfg.is_mla
    p = attn.mla_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    assert set(p) >= {"wq_a", "q_a_norm", "wq_b"} and "wq" not in p
    p["q_a_norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                                  p["q_a_norm"].shape)
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 5, cfg.d_model))
    q = attn._mla_query(p, h, cfg, jnp.arange(5))
    qa = h @ p["wq_a"]
    qa = qa / jnp.sqrt(jnp.mean(qa * qa, -1, keepdims=True) + 1e-6) \
        * p["q_a_norm"]
    want = jnp.einsum("bsr,rhk->bhsk", qa, p["wq_b"])
    n = cfg.qk_nope_head_dim
    np.testing.assert_allclose(np.asarray(q[..., :n]),
                               np.asarray(want[..., :n]), atol=1e-5)
    pe = want[..., n:]
    pe = jnp.concatenate([pe[..., 0::2], pe[..., 1::2]], -1)
    from repro.models.layers import apply_rope
    np.testing.assert_allclose(
        np.asarray(q[..., n:]),
        np.asarray(apply_rope(pe, jnp.arange(5), cfg.rope_theta)),
        atol=1e-5)


def test_kimi_preset_is_the_published_config():
    c = get_config("kimi-k2-1t-a32b")
    assert (c.q_lora_rank, c.kv_lora_rank, c.num_experts,
            c.experts_per_token, c.n_shared_experts, c.first_k_dense,
            c.d_ff, c.routed_scaling_factor, c.router_scoring) == \
        (1536, 512, 384, 8, 1, 1, 18432, 2.827, "sigmoid")


def _engine():
    run = make_run_config("moonlight-16b-a3b", "decode_32k")
    run = dataclasses.replace(
        run, model=_moonlight(),
        shape=dataclasses.replace(run.shape, seq_len=64, global_batch=4))
    model = build_model(run.model)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, run, sampler_binding=ActiveCodeRegistry()
                         .bind("u", "sampler"))
    return run, model, params, engine


def test_turn_from_a_kept_cache_equals_a_fresh_generate():
    """Prefill two chunks of rows into one resident cache, feed an id at
    the prompt's end and decode: the same tokens as ``generate`` on the
    prompt plus that id; a second turn from the same position on the
    same cache gives them again."""
    run, model, params, engine = _engine()
    P, N = 16, 6
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, P), 0,
                                run.model.vocab_size)
    fed = jax.random.randint(jax.random.PRNGKey(2), (4,), 0,
                             run.model.vocab_size)
    fresh, _ = engine.generate(params, jnp.concatenate([prompt, fed[:, None]],
                                                       1), N + 1)
    cache = model.init_cache(4, 64, engine.ctx)
    for r0 in (0, 2):
        _, cache, _ = engine.prefill(params, prompt[r0:r0 + 2], cache=cache,
                                     row=r0)
    for _ in range(2):
        toks, cache, pos, info = engine.decode(params, fed, cache, P, N)
        np.testing.assert_array_equal(np.asarray(toks),
                                      np.asarray(fresh[:, :N]))
        assert int(pos) == P + N and len(info["sampler_md5s"]) == N


def test_expert_load_is_recorded_only_while_on():
    run, model, params, engine = _engine()
    prompt = jnp.zeros((4, 8), jnp.int32)
    engine.generate(params, prompt, 3)
    assert engine.expert_load.drain() == []
    engine.expert_load.on = True
    engine.generate(params, prompt, 3)
    loads = engine.expert_load.drain()
    cfg = run.model
    assert len(loads) == 2
    for x in loads:
        assert x.shape == (cfg.num_layers - 1, cfg.num_experts)
        assert (x.sum(-1) == 4 * cfg.experts_per_token).all()


def test_flash_qualifies_the_latent_width(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((1, 4, 256, 192), jnp.bfloat16)
    assert ops.flash_qualifies(q, q)
