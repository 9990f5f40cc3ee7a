"""Encoder-decoder transformer (whisper-large-v3 backbone).

The conv/mel frontend is a STUB per the assignment: the encoder consumes
precomputed frame embeddings [B, enc_seq, d], which the caller
supplies. Encoder = bidirectional MHA + GELU MLP with learned positions;
decoder = causal self-attention (RoPE) + cross-attention + GELU MLP.

Decode carries a self-KV cache plus per-layer *precomputed* cross K/V
(computed once at prefill — cross-attention weights never touch the
encoder output again during decoding).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import attention as attn
from repro.models import layers
from repro.models.blocks import ModelCtx


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _cast(tree, dtype):
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)


class EncDecCache(NamedTuple):
    self_kv: attn.KVLayerCache      # stacked [L, ...]
    cross_k: jax.Array              # [L, B, Hkv, Senc, hd]
    cross_v: jax.Array


class EncDec:
    def __init__(self, cfg: ModelConfig):
        cfg.validate()
        assert cfg.is_encoder_decoder
        self.cfg = cfg

    # ------------------------------------------------------------------ init
    def _enc_layer_init(self, key, dtype):
        cfg = self.cfg
        ks = jax.random.split(key, 2)
        return {
            "norm1": jnp.ones((cfg.d_model,), dtype),
            "attn": attn.attn_init(ks[0], cfg, dtype),
            "norm2": jnp.ones((cfg.d_model,), dtype),
            "mlp": layers.mlp_init(ks[1], cfg.d_model, cfg.d_ff, "gelu", dtype),
        }

    def _dec_layer_init(self, key, dtype):
        cfg = self.cfg
        ks = jax.random.split(key, 3)
        return {
            "norm1": jnp.ones((cfg.d_model,), dtype),
            "self_attn": attn.attn_init(ks[0], cfg, dtype),
            "norm_x": jnp.ones((cfg.d_model,), dtype),
            "cross_attn": attn.attn_init(ks[1], cfg, dtype),
            "norm2": jnp.ones((cfg.d_model,), dtype),
            "mlp": layers.mlp_init(ks[2], cfg.d_model, cfg.d_ff, "gelu", dtype),
        }

    def init(self, rng: jax.Array) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        k_embed, k_enc, k_dec, k_un, k_pos = jax.random.split(rng, 5)
        enc_keys = jax.random.split(k_enc, cfg.num_encoder_layers)
        dec_keys = jax.random.split(k_dec, cfg.num_layers)
        p: Dict[str, Any] = {
            "embed": layers.embed_init(k_embed, cfg.padded_vocab(), cfg.d_model,
                                       dtype),
            "enc_pos": layers.trunc_normal(
                k_pos, (cfg.encoder_seq, cfg.d_model), 0.02, dtype),
            "encoder": jax.vmap(
                lambda k: self._enc_layer_init(k, dtype))(enc_keys),
            "enc_norm": jnp.ones((cfg.d_model,), dtype),
            "decoder": jax.vmap(
                lambda k: self._dec_layer_init(k, dtype))(dec_keys),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
        }
        if not cfg.tie_embeddings:
            p["unembed"] = layers.embed_init(k_un, cfg.padded_vocab(),
                                             cfg.d_model, dtype)
        return p

    def param_axes(self) -> Dict[str, Any]:
        cfg = self.cfg
        aattn = attn.attn_axes(cfg)
        enc_layer = {
            "norm1": ("embed_act",),
            "attn": aattn,
            "norm2": ("embed_act",),
            "mlp": layers.mlp_axes("gelu"),
        }
        dec_layer = {
            "norm1": ("embed_act",),
            "self_attn": aattn,
            "norm_x": ("embed_act",),
            "cross_attn": aattn,
            "norm2": ("embed_act",),
            "mlp": layers.mlp_axes("gelu"),
        }

        def stack(tree):
            return jax.tree.map(
                lambda axes: ("layers",) + axes, tree,
                is_leaf=lambda x: isinstance(x, tuple)
                and all(isinstance(e, (str, type(None))) for e in x))

        a: Dict[str, Any] = {
            "embed": ("vocab", "embed"),
            "enc_pos": ("enc_seq", "embed_act"),
            "encoder": stack(enc_layer),
            "enc_norm": ("embed_act",),
            "decoder": stack(dec_layer),
            "final_norm": ("embed_act",),
        }
        if not cfg.tie_embeddings:
            a["unembed"] = ("vocab", "embed")
        return a

    # --------------------------------------------------------------- encoder
    def encode(self, p, frames: jax.Array, ctx: ModelCtx) -> jax.Array:
        """frames [B, Senc, d] (frontend stub output) -> enc hidden."""
        cfg = self.cfg
        x = frames.astype(cfg.dtype) + p["enc_pos"].astype(cfg.dtype)[None]
        x = ctx.act(x, "batch", "seq", "embed_act")

        def layer_fn(x, p_l):
            p_l = _cast(p_l, cfg.dtype)
            h = layers.rmsnorm(x, p_l["norm1"], cfg.norm_eps, ctx.norm_impl)
            x = x + ctx.act(
                attn.attn_apply(p_l["attn"], h, cfg, causal=False,
                                impl=ctx.attn_impl, rope=False,
                                mesh=ctx.mesh),
                "batch", "seq", "embed_act")
            h2 = layers.rmsnorm(x, p_l["norm2"], cfg.norm_eps, ctx.norm_impl)
            x = x + ctx.act(layers.mlp_apply(p_l["mlp"], h2, "gelu"),
                            "batch", "seq", "embed_act")
            return x, None

        body = _remat(layer_fn, ctx.remat_policy)
        x, _ = jax.lax.scan(body, x, p["encoder"])
        return layers.rmsnorm(x, _cast(p["enc_norm"], cfg.dtype), cfg.norm_eps,
                              ctx.norm_impl)

    # --------------------------------------------------------------- decoder
    def _unembed(self, p, x: jax.Array) -> jax.Array:
        table = p["embed"] if self.cfg.tie_embeddings else p["unembed"]
        return layers.unembed(x, table)

    def forward(self, p, tokens: jax.Array, frames: jax.Array, ctx: ModelCtx
                ) -> Tuple[jax.Array, jax.Array]:
        """Teacher-forced decode over full sequence. Returns (logits, aux=0)."""
        cfg = self.cfg
        enc = self.encode(p, frames, ctx)
        x = layers.embed_lookup(p["embed"], tokens, cfg.d_model)
        x = ctx.act(x.astype(cfg.dtype), "batch", "seq", "embed_act")

        def layer_fn(x, p_l):
            p_l = _cast(p_l, cfg.dtype)
            h = layers.rmsnorm(x, p_l["norm1"], cfg.norm_eps, ctx.norm_impl)
            x = x + ctx.act(
                attn.attn_apply(p_l["self_attn"], h, cfg, causal=True,
                                impl=ctx.attn_impl, mesh=ctx.mesh),
                "batch", "seq", "embed_act")
            hx = layers.rmsnorm(x, p_l["norm_x"], cfg.norm_eps, ctx.norm_impl)
            kv = attn.cross_kv(p_l["cross_attn"], enc, cfg)
            x = x + ctx.act(
                attn.attn_apply(p_l["cross_attn"], hx, cfg, causal=False,
                                rope=False, kv=kv, impl=ctx.attn_impl,
                                mesh=ctx.mesh),
                "batch", "seq", "embed_act")
            h2 = layers.rmsnorm(x, p_l["norm2"], cfg.norm_eps, ctx.norm_impl)
            x = x + ctx.act(layers.mlp_apply(p_l["mlp"], h2, "gelu"),
                            "batch", "seq", "embed_act")
            return x, None

        body = _remat(layer_fn, ctx.remat_policy)
        x, _ = jax.lax.scan(body, x, p["decoder"])
        x = layers.rmsnorm(x, _cast(p["final_norm"], cfg.dtype), cfg.norm_eps,
                           ctx.norm_impl)
        logits = self._unembed(p, x)
        return ctx.act(logits, "batch", "seq", "vocab"), \
            jnp.zeros((), jnp.float32)

    # ----------------------------------------------------------- serve paths
    def init_cache(self, batch: int, max_seq: int, ctx: ModelCtx
                   ) -> EncDecCache:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        kv = attn.init_kv_cache(cfg, batch, max_seq, dt)
        self_kv = jax.tree.map(
            lambda a: jnp.zeros((cfg.num_layers,) + a.shape, a.dtype), kv)
        xshape = (cfg.num_layers, batch, cfg.num_kv_heads, cfg.encoder_seq,
                  cfg.hd())
        return EncDecCache(self_kv=self_kv,
                           cross_k=jnp.zeros(xshape, dt),
                           cross_v=jnp.zeros(xshape, dt))

    def cache_axes(self) -> EncDecCache:
        kv_ax = attn.kv_cache_axes()
        stacked = jax.tree.map(
            lambda axes: ("layers",) + axes, kv_ax,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x))
        x_ax = ("layers", "batch", "kv_heads", "enc_seq", "head_dim")
        return EncDecCache(self_kv=stacked, cross_k=x_ax, cross_v=x_ax)

    def prefill(self, p, tokens: jax.Array, frames: jax.Array,
                cache: EncDecCache, ctx: ModelCtx
                ) -> Tuple[jax.Array, EncDecCache, jax.Array]:
        cfg = self.cfg
        enc = self.encode(p, frames, ctx)
        x = layers.embed_lookup(p["embed"], tokens, cfg.d_model)
        x = x.astype(cfg.dtype)
        S = tokens.shape[1]

        def layer_fn(x, xs):
            p_l, kv_cache = xs
            p_l = _cast(p_l, cfg.dtype)
            h = layers.rmsnorm(x, p_l["norm1"], cfg.norm_eps, ctx.norm_impl)
            positions = jnp.arange(S)
            q, k, v = attn._project_qkv(p_l["self_attn"], h, cfg, positions)
            new_kv = attn.KVLayerCache(
                jax.lax.dynamic_update_slice_in_dim(
                    kv_cache.k, k.astype(kv_cache.k.dtype), 0, axis=2),
                jax.lax.dynamic_update_slice_in_dim(
                    kv_cache.v, v.astype(kv_cache.v.dtype), 0, axis=2))
            a_out = ops.attention(q, k, v, causal=True, impl=ctx.attn_impl,
                                  mesh=ctx.mesh)
            x = x + jnp.einsum("bhsk,hkd->bsd", a_out, p_l["self_attn"]["wo"])
            hx = layers.rmsnorm(x, p_l["norm_x"], cfg.norm_eps, ctx.norm_impl)
            ck, cv = attn.cross_kv(p_l["cross_attn"], enc, cfg)
            x = x + attn.attn_apply(p_l["cross_attn"], hx, cfg, causal=False,
                                    rope=False, kv=(ck, cv),
                                    impl=ctx.attn_impl, mesh=ctx.mesh)
            h2 = layers.rmsnorm(x, p_l["norm2"], cfg.norm_eps, ctx.norm_impl)
            x = x + layers.mlp_apply(p_l["mlp"], h2, "gelu")
            return x, (new_kv, ck.astype(cache.cross_k.dtype),
                       cv.astype(cache.cross_v.dtype))

        x, (self_kv, cross_k, cross_v) = jax.lax.scan(
            layer_fn, x, (p["decoder"], cache.self_kv))
        x = layers.rmsnorm(x, _cast(p["final_norm"], cfg.dtype), cfg.norm_eps,
                           ctx.norm_impl)
        logits = self._unembed(p, x[:, -1])
        return logits, EncDecCache(self_kv, cross_k, cross_v), \
            jnp.asarray(S, jnp.int32)

    def decode_step(self, p, token: jax.Array, cache: EncDecCache,
                    pos: jax.Array, ctx: ModelCtx
                    ) -> Tuple[jax.Array, EncDecCache]:
        cfg = self.cfg
        x = layers.embed_lookup(p["embed"], token[:, None], cfg.d_model)
        x = x.astype(cfg.dtype)

        def layer_fn(carry, xs):
            x, pos = carry
            p_l, kv_cache, ck, cv = xs
            p_l = _cast(p_l, cfg.dtype)
            h = layers.rmsnorm(x, p_l["norm1"], cfg.norm_eps, ctx.norm_impl)
            if ctx.decode_attn_impl == "seqshard":
                a_out, new_kv = attn.attn_decode_seqshard(
                    p_l["self_attn"], h, kv_cache, pos, cfg, ctx.mesh,
                    axis=ctx.tp_axis)
            else:
                a_out, new_kv = attn.attn_decode(
                    p_l["self_attn"], h, kv_cache, pos, cfg,
                    impl=ctx.decode_attn_impl)
            x = x + a_out
            hx = layers.rmsnorm(x, p_l["norm_x"], cfg.norm_eps, ctx.norm_impl)
            x = x + attn.attn_apply(
                p_l["cross_attn"], hx, cfg, causal=False, rope=False,
                kv=(ck.astype(cfg.dtype), cv.astype(cfg.dtype)),
                impl=ctx.decode_attn_impl)
            h2 = layers.rmsnorm(x, p_l["norm2"], cfg.norm_eps, ctx.norm_impl)
            x = x + layers.mlp_apply(p_l["mlp"], h2, "gelu")
            return (x, pos), new_kv

        (x, _), self_kv = jax.lax.scan(
            layer_fn, (x, pos),
            (p["decoder"], cache.self_kv, cache.cross_k, cache.cross_v))
        x = layers.rmsnorm(x, _cast(p["final_norm"], cfg.dtype), cfg.norm_eps,
                           ctx.norm_impl)
        logits = self._unembed(p, x[:, 0])
        return logits, EncDecCache(self_kv, cache.cross_k, cache.cross_v)
