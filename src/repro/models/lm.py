"""Decoder-only language model over stacked layers (lax.scan).

Covers the dense / moe / ssm / hybrid / vlm families. Layers are stacked
along a leading "layers" dim so the HLO is depth-independent; remat
policy wraps the scanned body. Parameters are stored in
``cfg.param_dtype`` and cast to ``cfg.dtype`` per layer inside the scan
(the cast fuses into the layer compute — no full low-precision copy is
ever materialized).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import blocks, layers
from repro.models.blocks import LayerCache, ModelCtx


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)   # "full": save only layer inputs


def _cast(tree, dtype):
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)


class LM:
    def __init__(self, cfg: ModelConfig):
        cfg.validate()
        self.cfg = cfg

    # ------------------------------------------------------------------ init
    def init(self, rng: jax.Array) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        k_embed, k_layers, k_un, k_meta = jax.random.split(rng, 4)
        layer_keys = jax.random.split(k_layers, cfg.num_layers)
        p: Dict[str, Any] = {
            "embed": layers.embed_init(k_embed, cfg.padded_vocab(), cfg.d_model,
                                       dtype),
            "layers": jax.vmap(
                lambda k: blocks.block_init(k, cfg, dtype))(layer_keys),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
        }
        if not cfg.tie_embeddings:
            p["unembed"] = layers.embed_init(k_un, cfg.padded_vocab(),
                                             cfg.d_model, dtype)
        if cfg.n_meta_tokens:
            p["meta"] = layers.trunc_normal(
                k_meta, (cfg.n_meta_tokens, cfg.d_model),
                cfg.d_model ** -0.5, dtype)
        return p

    def param_axes(self) -> Dict[str, Any]:
        cfg = self.cfg
        per_layer = blocks.block_axes(cfg)
        stacked = jax.tree.map(
            lambda axes: ("layers",) + axes, per_layer,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x))
        a: Dict[str, Any] = {
            "embed": ("vocab", "embed"),
            "layers": stacked,
            "final_norm": ("embed_act",),
        }
        if not cfg.tie_embeddings:
            a["unembed"] = ("vocab", "embed")
        if cfg.n_meta_tokens:
            a["meta"] = (None, "embed_act")
        return a

    # --------------------------------------------------------------- helpers
    def _embed_tokens(self, p, tokens: jax.Array, ctx: ModelCtx) -> jax.Array:
        cfg = self.cfg
        x = layers.embed_lookup(p["embed"], tokens, cfg.d_model)
        x = x.astype(cfg.dtype)
        if cfg.n_meta_tokens:
            meta = jnp.broadcast_to(
                p["meta"].astype(cfg.dtype)[None],
                (x.shape[0], cfg.n_meta_tokens, cfg.d_model))
            x = jnp.concatenate([meta, x], axis=1)
        return ctx.act(x, "batch", "seq", "embed_act")

    @jax.named_scope("unembed")
    def _unembed(self, p, x: jax.Array) -> jax.Array:
        table = p["embed"] if self.cfg.tie_embeddings else p["unembed"]
        return layers.unembed(x, table)

    def _layer_inputs(self):
        cfg = self.cfg
        uw = blocks.uniform_window(cfg)
        windows = blocks.layer_windows(cfg)
        return uw, windows

    # --------------------------------------------------------------- forward
    def forward(self, p, tokens: jax.Array, ctx: ModelCtx
                ) -> Tuple[jax.Array, jax.Array]:
        """tokens [B,S] -> (logits fp32 [B,S,V], aux_loss scalar)."""
        cfg = self.cfg
        x = self._embed_tokens(p, tokens, ctx)
        uw, windows = self._layer_inputs()

        def layer_fn(x, xs):
            p_l, w = xs
            p_l = _cast(p_l, cfg.dtype)
            x, aux = blocks.block_apply(p_l, x, cfg, ctx,
                                        uw if uw is not None else w)
            return x, aux

        body = _remat(layer_fn, ctx.remat_policy)
        x, auxs = jax.lax.scan(body, x, (p["layers"], windows))
        x = layers.rmsnorm(x, _cast(p["final_norm"], cfg.dtype), cfg.norm_eps,
                           ctx.norm_impl)
        if cfg.n_meta_tokens:
            x = x[:, cfg.n_meta_tokens:]
        logits = self._unembed(p, x)
        return ctx.act(logits, "batch", "seq", "vocab"), auxs.sum()

    # ----------------------------------------------------------- serve paths
    def init_cache(self, batch: int, max_seq: int, ctx: ModelCtx
                   ) -> LayerCache:
        cfg = self.cfg
        template = blocks.init_layer_cache(
            cfg, batch, max_seq + cfg.n_meta_tokens, jnp.dtype(cfg.dtype),
            jnp.dtype(cfg.dtype))
        return jax.tree.map(
            lambda a: jnp.zeros((cfg.num_layers,) + a.shape, a.dtype),
            template)

    def cache_axes(self) -> LayerCache:
        per_layer = blocks.cache_axes(self.cfg)
        return jax.tree.map(
            lambda axes: ("layers",) + axes, per_layer,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x))

    def prefill(self, p, tokens: jax.Array, cache: LayerCache, ctx: ModelCtx
                ) -> Tuple[jax.Array, LayerCache, jax.Array]:
        """Fill the cache with the prompt; return (last-token logits [B,V],
        cache, next position)."""
        cfg = self.cfg
        x = self._embed_tokens(p, tokens, ctx)
        uw, windows = self._layer_inputs()

        def layer_fn(x, xs):
            p_l, w, cache_l = xs
            p_l = _cast(p_l, cfg.dtype)
            x, new_cache = blocks.block_prefill(
                p_l, x, cfg, ctx, uw if uw is not None else w, cache_l)
            return x, new_cache

        x, new_cache = jax.lax.scan(layer_fn, x,
                                    (p["layers"], windows, cache))
        x = layers.rmsnorm(x, _cast(p["final_norm"], cfg.dtype), cfg.norm_eps,
                           ctx.norm_impl)
        logits = self._unembed(p, x[:, -1])
        pos = jnp.asarray(tokens.shape[1] + cfg.n_meta_tokens, jnp.int32)
        return logits, new_cache, pos

    def decode_step(self, p, token: jax.Array, cache: LayerCache,
                    pos: jax.Array, ctx: ModelCtx
                    ) -> Tuple[jax.Array, LayerCache]:
        """token [B] ids; pos scalar absolute position (incl. meta offset).
        Returns (logits [B,V], new cache)."""
        cfg = self.cfg
        x = layers.embed_lookup(p["embed"], token[:, None], cfg.d_model)
        x = x.astype(cfg.dtype)
        uw, windows = self._layer_inputs()

        def layer_fn(carry, xs):
            x, pos = carry
            p_l, w, cache_l = xs
            p_l = _cast(p_l, cfg.dtype)
            x, new_cache = blocks.block_decode(
                p_l, x, cfg, ctx, uw if uw is not None else w, cache_l, pos)
            return (x, pos), new_cache

        (x, _), new_cache = jax.lax.scan(layer_fn, (x, pos),
                                         (p["layers"], windows, cache))
        x = layers.rmsnorm(x, _cast(p["final_norm"], cfg.dtype), cfg.norm_eps,
                           ctx.norm_impl)
        logits = self._unembed(p, x[:, 0])
        return logits, new_cache
