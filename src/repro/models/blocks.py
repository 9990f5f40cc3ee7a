"""Per-layer block assembly for all LM families.

One layer's params are a flat dict; lm.py stacks L copies along a
leading "layers" dim for lax.scan. The per-layer sliding window is a
traced int32 (0 = full attention) so heterogeneous layer schedules
(hymba's SWA + 3 global layers) still scan.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers, moe, ssm
from repro.sharding.specs import AxisRules, constrain


@dataclass(frozen=True)
class ModelCtx:
    """Everything a model fwd needs besides params: mesh + sharding rules
    and the caller's kernel overrides.

    Each kernel choice has one home. ``"auto"`` is decided in
    ``kernels/ops.py`` from what the call can observe: the backend, and
    for attention the shapes and mesh (``flash_qualifies``).
    ``moe_impl="auto"`` is decided in ``moe.moe_apply``: expert parallel
    under a mesh, dropless without. The defaults here are the one-device
    training step's; ``train.step.build_ctx`` adds only what the call
    site knows: the mesh and rules, and for decode the forward-only
    Pallas kernels (``ssd``, ``gmm``) and, under a mesh,
    sequence-sharded decode attention. Tests pass ``"ref"``,
    ``"blockwise"`` or ``moe_impl="dense"`` to pin a path."""
    mesh: Any = None
    rules: Optional[AxisRules] = None
    attn_impl: str = "auto"         # auto | blockwise | pallas | ref
    decode_attn_impl: str = "ref"   # ref | seqshard
    moe_impl: str = "auto"          # auto | dropless | ep | dense
    # ssd_scan_pallas and moe_gmm_pallas have no VJP (kernels/ops.py): the
    # training step takes their XLA paths
    ssd_impl: str = "xla"
    norm_impl: str = "auto"
    gmm_impl: str = "xla"
    tp_axis: str = "model"
    batch_axes: Tuple[str, ...] = ("pod", "data")
    remat_policy: str = "full"      # none | full | dots

    def act(self, x, *axes):
        return constrain(x, self.rules, axes, self.mesh)


class LayerCache(NamedTuple):
    """Uniform per-layer decode state; unused fields are size-0 arrays so
    the pytree structure is identical across layers (scan-stackable).
    ``kv``: keys and values (GQA); ``ssm``: recurrent state; ``latent``:
    latent attention's [B, kv_lora_rank + qk_rope_head_dim, T];
    ``expert_load``: tokens the last step routed to each expert [E]."""
    kv: attn.KVLayerCache
    ssm: ssm.SSMLayerCache
    latent: jax.Array
    expert_load: jax.Array


def _empty() -> jax.Array:
    return jnp.zeros((0,), jnp.float32)


def _empty_kv() -> attn.KVLayerCache:
    return attn.KVLayerCache(_empty(), _empty())


def _empty_ssm() -> ssm.SSMLayerCache:
    return ssm.SSMLayerCache(_empty(), _empty())


def _moe_mlp(cfg: ModelConfig, dense_mlp: bool) -> bool:
    """Whether a layer's MLP is the expert layer: MoE models' layers but
    their leading dense ones (``dense_mlp``)."""
    return cfg.is_moe and not dense_mlp


def _mlp(p, h, cfg: ModelConfig, ctx: ModelCtx, dense_mlp: bool):
    """The layer's MLP: (y, aux loss, tokens per expert or size 0)."""
    if _moe_mlp(cfg, dense_mlp):
        return moe.moe_apply(p["moe"], h, cfg, impl=ctx.moe_impl,
                             mesh=ctx.mesh, tp_axis=ctx.tp_axis,
                             batch_axes=ctx.batch_axes, gmm_impl=ctx.gmm_impl)
    kind = "gelu" if cfg.is_encoder_decoder else "swiglu"
    return (layers.mlp_apply(p["mlp"], h, kind), jnp.zeros((), jnp.float32),
            _empty())


# ---------------------------------------------------------------------------
# Init / axes
# ---------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig, dtype, dense_mlp: bool = False
               ) -> Dict[str, Any]:
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"norm1": jnp.ones((cfg.d_model,), dtype)}
    fam = cfg.family
    if fam == "ssm":
        p["ssm"] = ssm.ssm_init(ks[0], cfg, dtype)
        return p
    p["attn"] = (attn.mla_init(ks[1], cfg, dtype) if cfg.is_mla
                 else attn.attn_init(ks[1], cfg, dtype))
    p["norm2"] = jnp.ones((cfg.d_model,), dtype)
    if fam == "hybrid":
        p["ssm"] = ssm.ssm_init(ks[0], cfg, dtype)
        p["branch_norm_attn"] = jnp.ones((cfg.d_model,), dtype)
        p["branch_norm_ssm"] = jnp.ones((cfg.d_model,), dtype)
        p["mlp"] = layers.mlp_init(ks[2], cfg.d_model, cfg.d_ff, "swiglu",
                                   dtype)
        return p
    if _moe_mlp(cfg, dense_mlp):
        p["moe"] = moe.moe_init(ks[3], cfg, dtype)
    else:
        kind = "gelu" if cfg.is_encoder_decoder else "swiglu"
        p["mlp"] = layers.mlp_init(ks[2], cfg.d_model, cfg.d_ff, kind, dtype)
    return p


def block_axes(cfg: ModelConfig, dense_mlp: bool = False) -> Dict[str, Any]:
    a: Dict[str, Any] = {"norm1": ("embed_act",)}
    fam = cfg.family
    if fam == "ssm":
        a["ssm"] = ssm.ssm_axes(cfg)
        return a
    a["attn"] = attn.mla_axes(cfg) if cfg.is_mla else attn.attn_axes(cfg)
    a["norm2"] = ("embed_act",)
    if fam == "hybrid":
        a["ssm"] = ssm.ssm_axes(cfg)
        a["branch_norm_attn"] = ("embed_act",)
        a["branch_norm_ssm"] = ("embed_act",)
        a["mlp"] = layers.mlp_axes("swiglu")
        return a
    if _moe_mlp(cfg, dense_mlp):
        a["moe"] = moe.moe_axes(cfg)
    else:
        kind = "gelu" if cfg.is_encoder_decoder else "swiglu"
        a["mlp"] = layers.mlp_axes(kind)
    return a


# ---------------------------------------------------------------------------
# Forward (train / full-sequence)
# ---------------------------------------------------------------------------

def block_apply(p, x, cfg: ModelConfig, ctx: ModelCtx, window,
                dense_mlp: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x [B,S,d] -> (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    fam = cfg.family
    h = layers.rmsnorm(x, p["norm1"], cfg.norm_eps, ctx.norm_impl)
    if fam == "ssm":
        x = x + ctx.act(ssm.ssm_apply(p["ssm"], h, cfg, impl=ctx.ssd_impl),
                        "batch", "seq", "embed_act")
        return x, aux
    if fam == "hybrid":
        a = attn.attn_apply(p["attn"], h, cfg, window=window,
                            impl=ctx.attn_impl, prefix=cfg.n_meta_tokens,
                            mesh=ctx.mesh)
        s = ssm.ssm_apply(p["ssm"], h, cfg, impl=ctx.ssd_impl)
        mix = (layers.rmsnorm(a, p["branch_norm_attn"], cfg.norm_eps,
                              ctx.norm_impl)
               + layers.rmsnorm(s, p["branch_norm_ssm"], cfg.norm_eps,
                                ctx.norm_impl)) * 0.5
        x = x + ctx.act(mix, "batch", "seq", "embed_act")
        h2 = layers.rmsnorm(x, p["norm2"], cfg.norm_eps, ctx.norm_impl)
        x = x + ctx.act(layers.mlp_apply(p["mlp"], h2, "swiglu"),
                        "batch", "seq", "embed_act")
        return x, aux
    # dense / moe / vlm decoder layer
    if cfg.is_mla:
        a, _ = attn.mla_prefill(p["attn"], h, cfg, impl=ctx.attn_impl,
                                mesh=ctx.mesh)
    else:
        a = attn.attn_apply(p["attn"], h, cfg, window=window,
                            impl=ctx.attn_impl, mesh=ctx.mesh)
    x = x + ctx.act(a, "batch", "seq", "embed_act")
    h2 = layers.rmsnorm(x, p["norm2"], cfg.norm_eps, ctx.norm_impl)
    y, aux, _ = _mlp(p, h2, cfg, ctx, dense_mlp)
    x = x + ctx.act(y, "batch", "seq", "embed_act")
    return x, aux


# ---------------------------------------------------------------------------
# Prefill / decode
# ---------------------------------------------------------------------------

def init_layer_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype,
                     kv_dtype, dense_mlp: bool = False) -> LayerCache:
    fam = cfg.family
    kv = (attn.init_kv_cache(cfg, batch, max_seq, kv_dtype)
          if fam != "ssm" and not cfg.is_mla else _empty_kv())
    st = (ssm.init_ssm_cache(cfg, batch, dtype)
          if fam in ("ssm", "hybrid") else _empty_ssm())
    lat = (attn.init_latent_cache(cfg, batch, max_seq, kv_dtype)
           if cfg.is_mla else _empty())
    load = (jnp.zeros((cfg.num_experts,), jnp.int32)
            if _moe_mlp(cfg, dense_mlp) else _empty())
    return LayerCache(kv=kv, ssm=st, latent=lat, expert_load=load)


def cache_axes(cfg: ModelConfig, dense_mlp: bool = False) -> LayerCache:
    fam = cfg.family
    kv = attn.kv_cache_axes() if fam != "ssm" and not cfg.is_mla else \
        attn.KVLayerCache((None,), (None,))
    st = ssm.ssm_cache_axes() if fam in ("ssm", "hybrid") else \
        ssm.SSMLayerCache((None,), (None,))
    lat = ("batch", None, "kv_seq") if cfg.is_mla else (None,)
    return LayerCache(kv=kv, ssm=st, latent=lat, expert_load=(None,))


def block_prefill(p, x, cfg: ModelConfig, ctx: ModelCtx, window,
                  cache: LayerCache, dense_mlp: bool = False
                  ) -> Tuple[jax.Array, LayerCache]:
    """Full-sequence forward that also fills the decode cache.

    The KV (or latent) cache slots [0:S] are written; the SSM state
    comes from the chunked scan's final state.
    """
    fam = cfg.family
    B, S, d = x.shape
    h = layers.rmsnorm(x, p["norm1"], cfg.norm_eps, ctx.norm_impl)

    new_kv, new_ssm, new_lat = cache.kv, cache.ssm, cache.latent

    if fam in ("ssm", "hybrid"):
        s_out, new_ssm = ssm.ssm_prefill(p["ssm"], h, cfg, impl=ctx.ssd_impl)

    if cfg.is_mla:
        a_out, lat = attn.mla_prefill(p["attn"], h, cfg, impl=ctx.attn_impl,
                                      mesh=ctx.mesh)
        new_lat = jax.lax.dynamic_update_slice_in_dim(
            cache.latent, jnp.swapaxes(lat, 1, 2).astype(cache.latent.dtype),
            0, axis=2)
    elif fam != "ssm":
        with jax.named_scope("attention"):
            positions = jnp.arange(S)
            q, k, v = attn._project_qkv(p["attn"], h, cfg, positions)
            new_kv = attn.KVLayerCache(
                jax.lax.dynamic_update_slice_in_dim(
                    cache.kv.k, k.astype(cache.kv.k.dtype), 0, axis=2),
                jax.lax.dynamic_update_slice_in_dim(
                    cache.kv.v, v.astype(cache.kv.v.dtype), 0, axis=2))
            from repro.kernels import ops
            a_out = ops.attention(q, k, v, causal=True, window=window,
                                  impl=ctx.attn_impl,
                                  prefix=cfg.n_meta_tokens, mesh=ctx.mesh)
            a_out = jnp.einsum("bhsk,hkd->bsd", a_out, p["attn"]["wo"])

    return _finish_block(p, x, a_out if fam != "ssm" else None,
                         s_out if fam in ("ssm", "hybrid") else None, cfg,
                         ctx, cache, new_kv, new_ssm, new_lat, dense_mlp)


def _finish_block(p, x, a_out, s_out, cfg: ModelConfig, ctx: ModelCtx,
                  cache: LayerCache, new_kv, new_ssm, new_lat,
                  dense_mlp: bool) -> Tuple[jax.Array, LayerCache]:
    """The residual adds and the MLP after a prefill or decode step's
    mixers, with the new cache."""
    fam = cfg.family
    if fam == "ssm":
        return x + s_out, cache._replace(kv=new_kv, ssm=new_ssm)
    if fam == "hybrid":
        mix = (layers.rmsnorm(a_out, p["branch_norm_attn"], cfg.norm_eps,
                              ctx.norm_impl)
               + layers.rmsnorm(s_out, p["branch_norm_ssm"], cfg.norm_eps,
                                ctx.norm_impl)) * 0.5
        x = x + mix
        h2 = layers.rmsnorm(x, p["norm2"], cfg.norm_eps, ctx.norm_impl)
        x = x + layers.mlp_apply(p["mlp"], h2, "swiglu")
        return x, cache._replace(kv=new_kv, ssm=new_ssm)
    x = x + a_out
    h2 = layers.rmsnorm(x, p["norm2"], cfg.norm_eps, ctx.norm_impl)
    y, _, load = _mlp(p, h2, cfg, ctx, dense_mlp)
    return x + y, LayerCache(new_kv, new_ssm, new_lat,
                             load.astype(cache.expert_load.dtype))


def block_decode(p, x, cfg: ModelConfig, ctx: ModelCtx, window,
                 cache: LayerCache, layer, pos, dense_mlp: bool = False
                 ) -> Tuple[jax.Array, LayerCache]:
    """One-token step of layer ``layer``. x [B,1,d]; ``cache`` is the
    stack of every layer's (leading axis the layer), read in place and
    never written. Returns x and the layer's new entries, for
    ``write_rows``: the K and V rows [B, Hkv, 1, D] or the latent row
    [B, C, 1] at ``pos``, and the whole SSM state and expert load; a
    field the model does not use is size 0."""
    fam = cfg.family
    h = layers.rmsnorm(x, p["norm1"], cfg.norm_eps, ctx.norm_impl)
    rows = LayerCache(_empty_kv(), _empty_ssm(), _empty(),
                      jnp.zeros((0,), cache.expert_load.dtype))
    new_kv, new_ssm, new_lat = rows.kv, rows.ssm, rows.latent

    if fam in ("ssm", "hybrid"):
        s_out, new_ssm = ssm.ssm_decode(
            p["ssm"], h, jax.tree.map(lambda a: _at(a, layer), cache.ssm),
            cfg)
    if cfg.is_mla:
        a_out, new_lat = attn.mla_decode(p["attn"], h, cache.latent, layer,
                                         pos, cfg)
    elif fam != "ssm":
        if ctx.decode_attn_impl == "seqshard":
            # writes the row inside its shard_map, on the owning rank; the
            # row is read back from that copy of the layer
            a_out, kv_l = attn.attn_decode_seqshard(
                p["attn"], h, jax.tree.map(lambda a: _at(a, layer), cache.kv),
                pos, cfg, ctx.mesh, axis=ctx.tp_axis, window=window,
                prefix=cfg.n_meta_tokens)
            new_kv = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, pos, 1, axis=2),
                kv_l)
        else:
            a_out, new_kv = attn.attn_decode(
                p["attn"], h, cache.kv, pos, cfg, layer=layer, window=window,
                impl=ctx.decode_attn_impl, prefix=cfg.n_meta_tokens)

    return _finish_block(p, x, a_out if fam != "ssm" else None,
                         s_out if fam in ("ssm", "hybrid") else None, cfg,
                         ctx, rows, new_kv, new_ssm, new_lat, dense_mlp)


def _at(stack: jax.Array, layer) -> jax.Array:
    return jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)


def write_rows(cache: LayerCache, rows: LayerCache, pos) -> LayerCache:
    """The stacked cache after a decode step's layer scan gave back
    ``rows`` (``block_decode``'s new entries, stacked over the layers):
    the K, V and latent rows written at ``pos`` on their position axis
    (3 of [L, B, Hkv, T, D] and of [L, B, C, T]), in place where the
    cache is donated; the SSM state and expert load whole."""
    def at_pos(stack, row):
        if not stack.size:
            return stack
        return jax.lax.dynamic_update_slice_in_dim(stack, row, pos, axis=3)

    def whole(stack, new):
        return new if stack.size else stack

    with jax.named_scope("cache_write"):
        return LayerCache(
            kv=jax.tree.map(at_pos, cache.kv, rows.kv),
            ssm=jax.tree.map(whole, cache.ssm, rows.ssm),
            latent=at_pos(cache.latent, rows.latent),
            expert_load=whole(cache.expert_load, rows.expert_load))


def layer_windows(cfg: ModelConfig) -> jax.Array:
    """Per-layer window sizes [L] (0 = full attention)."""
    w = []
    for i in range(cfg.num_layers):
        if cfg.sliding_window and i not in cfg.global_attn_layers:
            w.append(cfg.sliding_window)
        else:
            w.append(0)
    return jnp.asarray(w, jnp.int32)


def uniform_window(cfg: ModelConfig) -> Optional[int]:
    """Static window if all layers share one (lets the flash kernel take it)."""
    ws = set()
    for i in range(cfg.num_layers):
        if cfg.sliding_window and i not in cfg.global_attn_layers:
            ws.add(cfg.sliding_window)
        else:
            ws.add(0)
    return ws.pop() if len(ws) == 1 else None
