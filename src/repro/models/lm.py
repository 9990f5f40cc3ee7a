"""Decoder-only language model over stacked layers (lax.scan).

Covers the dense / moe / ssm / hybrid / vlm families. Layers are stacked
along a leading "layers" dim so the HLO is depth-independent; remat
policy wraps the scanned body. A MoE model's leading dense layers
(``first_k_dense``), whose MLP has another shape, are a stack of their
own (params ``dense_layers``) scanned before the main one; the decode
cache is then a tuple of the two stacks' caches. Parameters are stored in
``cfg.param_dtype`` and cast to ``cfg.dtype`` per layer inside the scan
(the cast fuses into the layer compute — no full low-precision copy is
ever materialized).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

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


# kept in float32 whatever the compute dtype: the router's scores and
# its score-correction bias choose the experts
_KEEP_F32 = ("router", "router_bias")


def _cast(tree, dtype):
    def cast(path, a):
        if not jnp.issubdtype(a.dtype, jnp.floating) or \
                (path and getattr(path[-1], "key", None) in _KEEP_F32):
            return a
        return a.astype(dtype)
    return jax.tree_util.tree_map_with_path(cast, tree)


def _stacked(axes_tree):
    """Per-layer logical axes with the leading "layers" axis added."""
    return jax.tree.map(
        lambda axes: ("layers",) + axes, axes_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x))


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
            "final_norm": jnp.ones((cfg.d_model,), dtype),
        }
        for key, lo, n, dense in self._segments():
            p[key] = jax.vmap(lambda k: blocks.block_init(
                k, cfg, dtype, dense))(layer_keys[lo:lo + n])
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
        a: Dict[str, Any] = {
            "embed": ("vocab", "embed"),
            "final_norm": ("embed_act",),
        }
        for key, _, _, dense in self._segments():
            a[key] = _stacked(blocks.block_axes(cfg, dense))
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

    def _segments(self) -> List[Tuple[str, int, int, bool]]:
        """(params key, first layer, layer count, dense MLP) of each stack
        of like layers, in order."""
        cfg = self.cfg
        k = cfg.first_k_dense if cfg.is_moe else 0
        head = [("dense_layers", 0, k, True)] if k else []
        return head + [("layers", k, cfg.num_layers - k, False)]

    def _split_cache(self, cache) -> List[LayerCache]:
        return list(cache) if len(self._segments()) > 1 else [cache]

    def _join_cache(self, caches: List[LayerCache]):
        return tuple(caches) if len(caches) > 1 else caches[0]

    def expert_load(self, cache) -> Optional[jax.Array]:
        """Tokens the step that wrote ``cache`` routed to each expert,
        [MoE layers, E] int32; None for a model without experts."""
        loads = [c.expert_load for c in self._split_cache(cache)
                 if c.expert_load.size]
        return loads[0] if loads else None

    # --------------------------------------------------------------- forward
    def forward(self, p, tokens: jax.Array, ctx: ModelCtx
                ) -> Tuple[jax.Array, jax.Array]:
        """tokens [B,S] -> (logits fp32 [B,S,V], aux_loss scalar)."""
        cfg = self.cfg
        x = self._embed_tokens(p, tokens, ctx)
        uw, windows = self._layer_inputs()

        aux = jnp.zeros((), jnp.float32)
        for key, lo, n, dense in self._segments():
            def layer_fn(x, xs, dense=dense):
                p_l, w = xs
                p_l = _cast(p_l, cfg.dtype)
                return blocks.block_apply(p_l, x, cfg, ctx,
                                          uw if uw is not None else w, dense)

            body = _remat(layer_fn, ctx.remat_policy)
            x, auxs = jax.lax.scan(body, x, (p[key], windows[lo:lo + n]))
            aux = aux + auxs.sum()
        x = layers.rmsnorm(x, _cast(p["final_norm"], cfg.dtype), cfg.norm_eps,
                           ctx.norm_impl)
        if cfg.n_meta_tokens:
            x = x[:, cfg.n_meta_tokens:]
        logits = self._unembed(p, x)
        return ctx.act(logits, "batch", "seq", "vocab"), aux

    # ----------------------------------------------------------- serve paths
    def init_cache(self, batch: int, max_seq: int, ctx: ModelCtx):
        """A LayerCache stacked over the layers (a tuple of one per stack
        where the model has leading dense layers)."""
        cfg = self.cfg
        caches = []
        for _, _, n, dense in self._segments():
            template = blocks.init_layer_cache(
                cfg, batch, max_seq + cfg.n_meta_tokens, jnp.dtype(cfg.dtype),
                jnp.dtype(cfg.dtype), dense)
            caches.append(jax.tree.map(
                lambda a, n=n: jnp.zeros((n,) + a.shape, a.dtype), template))
        return self._join_cache(caches)

    def cache_axes(self):
        return self._join_cache([
            _stacked(blocks.cache_axes(self.cfg, dense))
            for _, _, _, dense in self._segments()])

    def prefill(self, p, tokens: jax.Array, cache: LayerCache, ctx: ModelCtx
                ) -> Tuple[jax.Array, LayerCache, jax.Array]:
        """Fill the cache with the prompt; return (last-token logits [B,V],
        cache, next position)."""
        cfg = self.cfg
        x = self._embed_tokens(p, tokens, ctx)
        uw, windows = self._layer_inputs()

        new = []
        for (key, lo, n, dense), cache_s in zip(self._segments(),
                                                self._split_cache(cache)):
            def layer_fn(x, xs, dense=dense):
                p_l, w, cache_l = xs
                p_l = _cast(p_l, cfg.dtype)
                return blocks.block_prefill(
                    p_l, x, cfg, ctx, uw if uw is not None else w, cache_l,
                    dense)

            x, cache_s = jax.lax.scan(layer_fn, x,
                                      (p[key], windows[lo:lo + n], cache_s))
            new.append(cache_s)
        x = layers.rmsnorm(x, _cast(p["final_norm"], cfg.dtype), cfg.norm_eps,
                           ctx.norm_impl)
        logits = self._unembed(p, x[:, -1])
        pos = jnp.asarray(tokens.shape[1] + cfg.n_meta_tokens, jnp.int32)
        return logits, self._join_cache(new), pos

    def decode_step(self, p, token: jax.Array, cache: LayerCache,
                    pos: jax.Array, ctx: ModelCtx
                    ) -> Tuple[jax.Array, LayerCache]:
        """token [B] ids; pos scalar absolute position (incl. meta offset).
        Returns (logits [B,V], new cache).

        The layer scan reads each stack's cache in place and never writes
        it: a layer gives back only its new entries, and one write after
        the scan puts them into the stack (in place where the caller
        donates it), so no layer's or stack's cache is ever copied."""
        cfg = self.cfg
        x = layers.embed_lookup(p["embed"], token[:, None], cfg.d_model)
        x = x.astype(cfg.dtype)
        uw, windows = self._layer_inputs()

        new = []
        for (key, lo, n, dense), cache_s in zip(self._segments(),
                                                self._split_cache(cache)):
            def layer_fn(x, xs, dense=dense, cache_s=cache_s):
                p_l, w, layer = xs
                p_l = _cast(p_l, cfg.dtype)
                return blocks.block_decode(
                    p_l, x, cfg, ctx, uw if uw is not None else w, cache_s,
                    layer, pos, dense)

            x, rows = jax.lax.scan(
                layer_fn, x,
                (p[key], windows[lo:lo + n], jnp.arange(n, dtype=jnp.int32)))
            new.append(blocks.write_rows(cache_s, rows, pos))
        x = layers.rmsnorm(x, _cast(p["final_norm"], cfg.dtype), cfg.norm_eps,
                           ctx.norm_impl)
        logits = self._unembed(p, x[:, 0])
        return logits, self._join_cache(new)
