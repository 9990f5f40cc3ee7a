"""Attention block: GQA projections, optional qk-norm, RoPE, KV cache.

Train/prefill call into kernels.ops.attention (flash or blockwise);
decode does a cache update + masked attention over the cache.
Logical axes: heads are tensor-parallel ("heads" -> model axis), the
embed dim of every projection is the FSDP dim.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import layers


def attn_init(key, cfg: ModelConfig, dtype) -> Dict[str, jax.Array]:
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd()
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], d, (hq, hd), dtype),
        "wk": layers.dense_init(ks[1], d, (hkv, hd), dtype),
        "wv": layers.dense_init(ks[2], d, (hkv, hd), dtype),
        "wo": layers.trunc_normal(ks[3], (hq, hd, d), (hq * hd) ** -0.5, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attn_axes(cfg: ModelConfig) -> Dict[str, Tuple[str, ...]]:
    a = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qk_norm:
        a["q_norm"] = ("head_dim",)
        a["k_norm"] = ("head_dim",)
    return a


def _project_qkv(p, x, cfg: ModelConfig, positions, *, rope: bool = True):
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"])
    if cfg.qk_norm:
        q = layers.rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = layers.rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


@jax.named_scope("attention")
def attn_apply(
    p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig, *,
    causal: bool = True, window: int = 0, impl: str = "blockwise",
    rope: bool = True, positions: Optional[jax.Array] = None,
    kv: Optional[Tuple[jax.Array, jax.Array]] = None, prefix: int = 0,
    mesh=None,
) -> jax.Array:
    """Full-sequence attention (train / prefill / encoder).

    ``kv`` overrides keys/values (cross-attention: precomputed from the
    encoder). x [B,S,d] -> [B,S,d].
    """
    B, S, d = x.shape
    if positions is None:
        positions = jnp.arange(S)
    if kv is None:
        q, k, v = _project_qkv(p, x, cfg, positions, rope=rope)
    else:
        q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
        if cfg.qk_norm:
            q = layers.rmsnorm(q, p["q_norm"], cfg.norm_eps)
        if rope:
            q = layers.apply_rope(q, positions, cfg.rope_theta)
        k, v = kv
    out = ops.attention(q, k, v, causal=causal, window=window, impl=impl,
                        prefix=prefix, mesh=mesh)
    return jnp.einsum("bhsk,hkd->bsd", out, p["wo"])


def cross_kv(p: Dict[str, jax.Array], enc: jax.Array, cfg: ModelConfig,
             rope: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Precompute cross-attention K/V from encoder output [B,Senc,d]."""
    k = jnp.einsum("bsd,dhk->bhsk", enc, p["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", enc, p["wv"])
    if cfg.qk_norm:
        k = layers.rmsnorm(k, p["k_norm"], cfg.norm_eps)
    return k, v


# ---------------------------------------------------------------------------
# Decode path (single new token against a KV cache)
# ---------------------------------------------------------------------------

class KVLayerCache(NamedTuple):
    k: jax.Array        # [B, Hkv, Smax, D]
    v: jax.Array


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: int,
                  dtype) -> KVLayerCache:
    shape = (batch, cfg.num_kv_heads, max_seq, cfg.hd())
    return KVLayerCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def kv_cache_axes() -> KVLayerCache:
    return KVLayerCache(("batch", "kv_heads", "kv_seq", "head_dim"),
                        ("batch", "kv_heads", "kv_seq", "head_dim"))


def with_row(stack: jax.Array, layer, row: jax.Array, pos, axis: int
             ) -> jax.Array:
    """Layer ``layer`` of a stacked cache as a decode step reads it: the
    stack read in place, with ``row`` in place of position ``pos`` on
    ``axis`` (of the layer's array). The select is elementwise, so it
    fuses into its reader; the stack itself is written after the layer
    scan (``blocks.write_rows``), and rows at positions past ``pos`` may
    be stale from an earlier turn."""
    cur = jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
    t = jax.lax.broadcasted_iota(jnp.int32, cur.shape, axis)
    return jnp.where(t == pos, row, cur)


@jax.named_scope("attention")
def attn_decode(
    p: Dict[str, jax.Array], x: jax.Array, cache: KVLayerCache,
    pos: jax.Array, cfg: ModelConfig, *, layer=None,
    window: int = 0, impl: str = "ref", rope: bool = True, prefix: int = 0,
) -> Tuple[jax.Array, KVLayerCache]:
    """x [B,1,d]; pos [] scalar current position. Without ``layer``,
    ``cache`` is one layer's, and (out, cache with the new K and V
    written) is returned. With it, ``cache`` is the stack of every
    layer's [L, B, Hkv, T, D], read in place and not written, and (out,
    the new K and V rows [B, Hkv, 1, D]) is returned."""
    B = x.shape[0]
    positions = jnp.full((1,), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, rope=rope)
    k_new = k_new.astype(cache.k.dtype)
    v_new = v_new.astype(cache.v.dtype)
    if layer is None:
        k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new, pos, axis=2)
        v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new, pos, axis=2)
        new = KVLayerCache(k, v)
    else:
        k = with_row(cache.k, layer, k_new, pos, axis=2)
        v = with_row(cache.v, layer, v_new, pos, axis=2)
        new = KVLayerCache(k_new, v_new)
    if not (isinstance(window, int) and window == 0):
        # sliding-window decode: band mask pos-window < j <= pos
        w = jnp.asarray(window)
        k_posn = jnp.arange(k.shape[2])
        band = (k_posn <= pos) & (((pos - k_posn) < w) | (w <= 0))
        if prefix:
            band |= (k_posn < prefix) & (k_posn <= pos)
        out = _masked_decode(q, k.astype(q.dtype), v.astype(q.dtype),
                             band[None, None, None, :])
    else:
        kv_len = jnp.full((B,), pos + 1, jnp.int32)
        out = ops.attention(q, k.astype(q.dtype), v.astype(q.dtype),
                            causal=False, window=0, impl=impl, kv_len=kv_len)
    y = jnp.einsum("bhsk,hkd->bsd", out, p["wo"])
    return y, new


@jax.named_scope("attention")
def attn_decode_seqshard(
    p: Dict[str, jax.Array], x: jax.Array, cache: KVLayerCache,
    pos: jax.Array, cfg: ModelConfig, mesh, *,
    axis: str = "model", window: int = 0, rope: bool = True, prefix: int = 0,
) -> Tuple[jax.Array, KVLayerCache]:
    """Flash-decode over a sequence-sharded KV cache.

    cache.k/v [B, Hkv, S, D] are sharded over S on mesh axis ``axis``
    (kv_heads never divide 16 on the assigned archs, and at batch 1 the
    data axis is idle — the seq dim is the only way to spread a 500k KV).
    Each rank computes a partial online-softmax over its KV slice; the
    merge is one pmax + two psums of [B, Hq, D]-sized partials — O(B*H*D)
    bytes on the wire instead of all-gathering the O(B*Hkv*S*D) cache.
    The new token's K/V is written by the owning rank only.
    """
    from jax.sharding import PartitionSpec as P

    B = x.shape[0]
    n = mesh.shape[axis]
    S = cache.k.shape[2]
    assert S % n == 0, (S, n)
    slice_len = S // n
    positions = jnp.full((1,), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, rope=rope)
    scale = cfg.hd() ** -0.5

    def body(q, k_new, v_new, k_sl, v_sl):
        r = jax.lax.axis_index(axis)
        start = r * slice_len
        local = pos - start
        own = (local >= 0) & (local < slice_len)
        loc = jnp.clip(local, 0, slice_len - 1)
        k_upd = jax.lax.dynamic_update_slice_in_dim(
            k_sl, k_new.astype(k_sl.dtype), loc, axis=2)
        v_upd = jax.lax.dynamic_update_slice_in_dim(
            v_sl, v_new.astype(v_sl.dtype), loc, axis=2)
        k_sl = jnp.where(own, k_upd, k_sl)
        v_sl = jnp.where(own, v_upd, v_sl)

        k_pos = start + jnp.arange(slice_len)
        mask = k_pos <= pos
        if not (isinstance(window, int) and window == 0):
            w = jnp.asarray(window)
            band = (pos - k_pos) < w
            if prefix:
                band |= k_pos < prefix
            mask &= band | (w <= 0)

        # grouped-q GQA: never materialize a q-head-expanded (or f32)
        # copy of the cache — bf16 cache streams straight into the dots
        # with fp32 accumulation (preferred_element_type).
        Hkv = k_sl.shape[1]
        group = q.shape[1] // Hkv
        qg = q.reshape(q.shape[0], Hkv, group, q.shape[3])    # Sq==1
        logits = jnp.einsum("bhgd,bhkd->bhgk", qg, k_sl,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(mask[None, None, None, :], logits, -1e30)
        m = logits.max(axis=-1)                               # [B,Hkv,g]
        pr = jnp.exp(logits - m[..., None])
        pr = jnp.where(mask[None, None, None, :], pr, 0.0)
        l = pr.sum(axis=-1)
        acc = jnp.einsum("bhgk,bhkd->bhgd", pr.astype(v_sl.dtype), v_sl,
                         preferred_element_type=jnp.float32)
        m_g = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, axis)
        acc_g = jax.lax.psum(acc * corr[..., None], axis)
        out = (acc_g / jnp.maximum(l_g, 1e-30)[..., None])
        out = out.reshape(q.shape[0], q.shape[1], 1,
                          q.shape[3]).astype(x.dtype)
        return out, k_sl, v_sl

    out, k_c, v_c = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(None, None, axis, None),
                  P(None, None, axis, None)),
        out_specs=(P(), P(None, None, axis, None),
                   P(None, None, axis, None)),
        axis_names={axis}, check_vma=False,
    )(q, k_new, v_new, cache.k, cache.v)
    y = jnp.einsum("bhsk,hkd->bsd", out, p["wo"])
    return y, KVLayerCache(k_c, v_c)


def _masked_decode(q, k, v, mask):
    group = q.shape[1] // k.shape[1]
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kr.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs,
                      vr.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Latent attention (MLA, DeepSeek-V3)
# ---------------------------------------------------------------------------
#
# A layer caches one latent per position, [c_kv | k_pe]: the normed
# low-rank c_kv (kv_lora_rank) and the roped key part k_pe
# (qk_rope_head_dim), both shared by every head. The cache is laid out
# [B, kv_lora_rank + qk_rope_head_dim, T], positions minor, so that the
# 576-wide latent is not padded to the lanes (kernels/mla_decode.py). The prefill expands
# c_kv through W_UK (``wk_b``) and W_UV (``wv_b``) to per-head keys and
# values and runs causal attention at q/k width nope + rope and v width
# v_head_dim; decode folds W_UK into the query and W_UV into the output
# and attends over the latent rows themselves.

# the q_a / kv_a norms are built with RMSNorm's default eps in the
# DeepSeek-V3 modelling code, not the config's rms_norm_eps
LATENT_NORM_EPS = 1e-6


def mla_init(key, cfg: ModelConfig, dtype) -> Dict[str, jax.Array]:
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    qk, vd = cfg.qk_head_dim(), cfg.v_head_dim
    ks = jax.random.split(key, 6)
    p: Dict[str, jax.Array] = {}
    if cfg.q_lora_rank:
        p["wq_a"] = layers.dense_init(ks[0], d, (cfg.q_lora_rank,), dtype)
        p["q_a_norm"] = jnp.ones((cfg.q_lora_rank,), dtype)
        p["wq_b"] = layers.dense_init(ks[1], cfg.q_lora_rank, (H, qk), dtype)
    else:
        p["wq"] = layers.dense_init(ks[0], d, (H, qk), dtype)
    p["wkv_a"] = layers.dense_init(ks[2], d, (cfg.latent_width(),), dtype)
    p["kv_a_norm"] = jnp.ones((r,), dtype)
    p["wk_b"] = layers.dense_init(ks[3], r, (H, cfg.qk_nope_head_dim), dtype)
    p["wv_b"] = layers.dense_init(ks[4], r, (H, vd), dtype)
    p["wo"] = layers.trunc_normal(ks[5], (H, vd, d), (H * vd) ** -0.5, dtype)
    return p


def mla_axes(cfg: ModelConfig) -> Dict[str, Tuple]:
    a: Dict[str, Tuple] = {}
    if cfg.q_lora_rank:
        a["wq_a"] = ("embed", None)
        a["q_a_norm"] = (None,)
        a["wq_b"] = (None, "heads", "head_dim")
    else:
        a["wq"] = ("embed", "heads", "head_dim")
    a.update(wkv_a=("embed", None), kv_a_norm=(None,),
             wk_b=(None, "heads", "head_dim"),
             wv_b=(None, "heads", "head_dim"),
             wo=("heads", "head_dim", "embed"))
    return a


def init_latent_cache(cfg: ModelConfig, batch: int, max_seq: int,
                      dtype) -> jax.Array:
    return jnp.zeros((batch, cfg.latent_width(), max_seq), dtype)


def _rope_published(x, positions, theta):
    """RoPE on DeepSeek-V3's interleaved layout: the modelling code
    de-interleaves the pairs (x0, x1), (x2, x3), ... into halves and then
    rotates halves. q and k go through the same permutation, so their dot
    products are those of the published model."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return layers.apply_rope(x, positions, theta)


def _mla_query(p, h, cfg: ModelConfig, positions):
    """h [B,S,d] -> q [B,H,S,nope + rope] with its rope part rotated."""
    if cfg.q_lora_rank:
        qa = layers.rmsnorm(jnp.einsum("bsd,dr->bsr", h, p["wq_a"]),
                            p["q_a_norm"], LATENT_NORM_EPS)
        q = jnp.einsum("bsr,rhk->bhsk", qa, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bhsk", h, p["wq"])
    n = cfg.qk_nope_head_dim
    return jnp.concatenate(
        [q[..., :n], _rope_published(q[..., n:], positions, cfg.rope_theta)],
        axis=-1)


def _mla_latent(p, h, cfg: ModelConfig, positions):
    """h [B,S,d] -> latent rows [B,S,r + rope]: normed c_kv, roped k_pe."""
    kv = jnp.einsum("bsd,dc->bsc", h, p["wkv_a"])
    r = cfg.kv_lora_rank
    c = layers.rmsnorm(kv[..., :r], p["kv_a_norm"], LATENT_NORM_EPS)
    k_pe = _rope_published(kv[..., r:], positions, cfg.rope_theta)
    return jnp.concatenate([c, k_pe], axis=-1)


@jax.named_scope("attention")
def mla_prefill(p, h, cfg: ModelConfig, *, impl: str = "auto", mesh=None
                ) -> Tuple[jax.Array, jax.Array]:
    """Non-absorbed causal latent attention over a whole sequence.
    h [B,S,d] -> (out [B,S,d], latents [B,S,r + rope] to cache)."""
    with jax.named_scope("mla_prefill"):
        B, S, _ = h.shape
        positions = jnp.arange(S)
        q = _mla_query(p, h, cfg, positions)
        lat = _mla_latent(p, h, cfg, positions)
        r, H = cfg.kv_lora_rank, cfg.num_heads
        c = lat[..., :r]
        k_pe = jnp.broadcast_to(lat[:, None, :, r:],
                                (B, H, S, cfg.qk_rope_head_dim))
        k = jnp.concatenate(
            [jnp.einsum("bsr,rhk->bhsk", c, p["wk_b"]), k_pe], axis=-1)
        v = jnp.einsum("bsr,rhk->bhsk", c, p["wv_b"])
        # V zero-padded to the q/k width, so that one kernel (or the
        # blockwise path) takes the call; the padding's output is dropped
        vd, qk = cfg.v_head_dim, cfg.qk_head_dim()
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk - vd)))
        out = ops.attention(q, k, v, causal=True, scale=qk ** -0.5,
                            impl=impl, mesh=mesh)[..., :vd]
        return jnp.einsum("bhsk,hkd->bsd", out, p["wo"]), lat


@jax.named_scope("attention")
def mla_decode(p, h, cache: jax.Array, layer, pos, cfg: ModelConfig, *,
               impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """One absorbed decode step of layer ``layer``. h [B,1,d]; cache
    [L,B,r + rope,T] every layer's latents, read in place and not
    written; pos [] the new token's position. The query folds in W_UK,
    the output W_UV, and the attention reads the latents only (the new
    one at ``pos``), never per-head keys or values. Returns (out
    [B,1,d], the new latent row [B,r + rope,1])."""
    with jax.named_scope("mla_decode"):
        positions = jnp.full((1,), pos, jnp.int32)
        q = _mla_query(p, h, cfg, positions)[:, :, 0]          # [B,H,qk]
        row = jnp.swapaxes(_mla_latent(p, h, cfg, positions),
                           1, 2).astype(cache.dtype)          # [B,C,1]
        n, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        q_lat = jnp.einsum("bhk,rhk->bhr", q[..., :n], p["wk_b"],
                           preferred_element_type=jnp.float32)
        q_abs = jnp.concatenate([q_lat.astype(cache.dtype),
                                 q[..., n:].astype(cache.dtype)], axis=-1)
        o_lat = ops.latent_decode_attention(
            q_abs, cache, layer, row, pos, r=r,
            scale=cfg.qk_head_dim() ** -0.5, impl=impl)
        o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(h.dtype), p["wv_b"])
        y = jnp.einsum("bhv,hvd->bd", o, p["wo"])
        return y[:, None], row
