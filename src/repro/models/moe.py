"""Mixture-of-Experts layer: top-k router + two dispatch paths.

* ``dense`` — every expert computes every token, combined with the
  top-k gate mask. Exact semantics, E/k-times wasteful; used as the
  numerics oracle and for tiny smoke configs only.

* ``ep`` — TPU-native expert parallelism in ``shard_map``:
    1. the token batch enters sequence-split over the ``model`` axis
       (doubling as sequence parallelism for the MoE block);
    2. local sort-based grouping (argsort by expert id — no
       GShard-style [tokens, E, C] one-hot dispatch einsum, whose FLOP
       cost rivals the expert matmul itself at E=384);
    3. fixed-capacity scatter into [E, C, d] buffers (static shapes for
       pjit; overflow tokens drop, underflow pads — capacity_factor
       controls drop rate);
    4. ``all_to_all`` over ``model`` moves each expert's buffer to its
       owner (E sharded model-wise);
    5. grouped matmul (kernels.ops.gmm — Pallas on TPU);
    6. reverse all_to_all, unsort, gate-weighted combine.

* decode (S == 1) uses a replicated-token variant: model ranks compute
  their local experts on the (small) replicated token set and psum the
  gate-weighted partial outputs — no all_to_all at trivial token counts.

* ``dropless`` — one device holding every expert: tokens sorted by
  expert and each expert's rows multiplied as one group of a ragged
  grouped matmul (``kernels.ops.ragged_gmm``: megablox on TPU,
  ``lax.ragged_dot`` elsewhere). Nothing is padded to a capacity and
  nothing is dropped.

Router runs in fp32, by softmax or (DeepSeek-V3 ``noaux_tc``) by sigmoid
scores whose choice adds a score-correction bias that the gates leave
out; an auxiliary load-balance loss (Switch-style) is returned
alongside. Shared experts (one SwiGLU MLP) add to every token's output.
``moe_apply`` also returns the step's tokens per expert.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import layers


def moe_init(key, cfg: ModelConfig, dtype) -> Dict[str, jax.Array]:
    d, E, ff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": layers.trunc_normal(ks[0], (d, E), d ** -0.5, jnp.float32),
        "w_gate": layers.trunc_normal(ks[1], (E, d, ff), d ** -0.5, dtype),
        "w_up": layers.trunc_normal(ks[2], (E, d, ff), d ** -0.5, dtype),
        "w_down": layers.trunc_normal(ks[3], (E, ff, d), ff ** -0.5, dtype),
    }
    if cfg.router_scoring == "sigmoid":
        p["router_bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = layers.mlp_init(ks[4], d, cfg.n_shared_experts * ff,
                                      "swiglu", dtype)
    return p


def moe_axes(cfg: ModelConfig) -> Dict[str, Any]:
    a: Dict[str, Any] = {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "expert_ffn"),
        "w_up": ("experts", "embed", "expert_ffn"),
        "w_down": ("experts", "expert_ffn", "embed"),
    }
    if cfg.router_scoring == "sigmoid":
        a["router_bias"] = (None,)
    if cfg.n_shared_experts:
        a["shared"] = layers.mlp_axes("swiglu")
    return a


def _router_params(p) -> Dict[str, jax.Array]:
    return {k: p[k] for k in ("router", "router_bias") if k in p}


@jax.named_scope("moe_router")
def _route(p, x, cfg: ModelConfig):
    """x [..., d] -> (topk_gates [..., k], topk_idx [..., k], aux_loss)."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + p["router_bias"],
                               cfg.experts_per_token)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        probs = scores / scores.sum(-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    gates = gates * cfg.routed_scaling_factor
    # Switch-style load-balance aux loss
    E = cfg.num_experts
    me = probs.mean(axis=tuple(range(probs.ndim - 1)))          # [E]
    ce = jax.nn.one_hot(idx[..., 0], E).mean(
        axis=tuple(range(idx.ndim - 1)))                        # top-1 counts
    aux = E * jnp.sum(me * ce)
    return gates, idx, aux


def _expert_ffn(w_gate, w_up, w_down, h, impl: str):
    """h [E, C, d] -> [E, C, d] SwiGLU per expert via grouped matmul."""
    g = ops.gmm(h, w_gate, impl=impl)
    u = ops.gmm(h, w_up, impl=impl)
    act = (jax.nn.silu(g.astype(jnp.float32)) *
           u.astype(jnp.float32)).astype(h.dtype)
    return ops.gmm(act, w_down, impl=impl)


# ---------------------------------------------------------------------------
# Dense (oracle) path
# ---------------------------------------------------------------------------

def moe_apply_dense(p, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """All experts on all tokens; gate-masked combine. x [B,S,d]."""
    gates, idx, aux = _route(p, x, cfg)
    g = jnp.einsum("...k,...ke->...e", gates,
                   jax.nn.one_hot(idx, cfg.num_experts))        # [B,S,E]
    up = jnp.einsum("bsd,edf->bsef", x, p["w_up"])
    gt = jnp.einsum("bsd,edf->bsef", x, p["w_gate"])
    h = jax.nn.silu(gt.astype(jnp.float32)) * up.astype(jnp.float32)
    y = jnp.einsum("bsef,efd->bsed", h.astype(x.dtype), p["w_down"])
    out = jnp.einsum("bse,bsed->bsd", g.astype(x.dtype), y)
    return out, aux


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path
# ---------------------------------------------------------------------------

def _capacity(tokens: int, cfg: ModelConfig, n_shards: int) -> int:
    """Per-expert capacity of the local dispatch buffer."""
    c = int(tokens * cfg.experts_per_token * cfg.capacity_factor
            / cfg.num_experts) + 1
    return max(4, -(-c // 4) * 4)   # pad to a multiple of 4


def _local_group(x_l, gates, idx, E: int, C: int):
    """Sort-based dispatch of local tokens into [E, C, d] buffers.

    x_l [T, d]; gates/idx [T, k]. Returns (buffers [E,C,d],
    inv_index [T*k] into flattened buffer (or -1 if dropped)).
    """
    T, d = x_l.shape
    k = idx.shape[-1]
    flat_e = idx.reshape(-1)                        # [T*k]
    order = jnp.argsort(flat_e, stable=True)        # tokens grouped by expert
    sorted_e = flat_e[order]
    # position within expert group
    pos_in_group = jnp.arange(T * k) - jnp.searchsorted(
        sorted_e, sorted_e, side="left")
    keep = pos_in_group < C
    dest = jnp.where(keep, sorted_e * C + pos_in_group, E * C)  # E*C = trash
    tok_of = order // k                              # source token per slot
    buf = jnp.zeros((E * C + 1, d), x_l.dtype).at[dest].set(
        x_l[tok_of], mode="drop")
    inv = jnp.full((T * k,), -1, jnp.int32).at[order].set(
        jnp.where(keep, dest, -1).astype(jnp.int32))
    return buf[:-1].reshape(E, C, d), inv


def _moe_ep_local(x_l, router, w_gate, w_up, w_down, *, cfg: ModelConfig,
                  axis: str, n_shards: int, gmm_impl: str):
    """shard_map body. x_l [B_l, S_l, d]; weights are the LOCAL expert
    shards [E_l, ...]. Returns (y_l, aux)."""
    B_l, S_l, d = x_l.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    E_l = w_gate.shape[0]
    x_f = x_l.reshape(-1, d)
    T = x_f.shape[0]
    gates, idx, aux = _route(router, x_f, cfg)
    C = _capacity(T, cfg, n_shards)

    buffers, inv = _local_group(x_f, gates, idx, E, C)       # [E, C, d]
    if n_shards > 1:
        # tiled all_to_all: split E (= n*E_l) into n chunks of [E_l,C,d],
        # deliver chunk j to rank j, concat received chunks along the C
        # axis -> [E_l, n*C, d] (slice [:, r*C:(r+1)*C] is rank r's
        # tokens). tiled=True also has a clean transpose for the VJP.
        h = jax.lax.all_to_all(buffers, axis, split_axis=0, concat_axis=1,
                               tiled=True)
    else:
        h = buffers

    y = _expert_ffn(w_gate, w_up, w_down, h, gmm_impl)       # [E_l, nC, d]

    if n_shards > 1:
        # inverse exchange: chunk r of the C axis goes home to rank r;
        # received blocks stack e_global-major along the expert axis.
        back = jax.lax.all_to_all(y, axis, split_axis=1, concat_axis=0,
                                  tiled=True)                # [E, C, d]
        y_full = back.reshape(E * C, d)                      # e_global-major
    else:
        y_full = y.reshape(E * C, d)

    # gather back to (token, choice) slots; dropped slots -> 0
    flat = jnp.where(inv[:, None] >= 0,
                     y_full[jnp.maximum(inv, 0)], 0.0)       # [T*k, d]
    y_tok = (flat.reshape(T, k, d).astype(jnp.float32)
             * gates[..., None]).sum(axis=1)
    return y_tok.reshape(B_l, S_l, d).astype(x_l.dtype), aux


def _moe_decode_local(x_l, router, w_gate, w_up, w_down, *, cfg: ModelConfig,
                      axis: str, n_shards: int, shard_id, gmm_impl: str):
    """Replicated-token decode path: each model rank computes its local
    experts on all (few) tokens, partial outputs psum'd."""
    B_l, S_l, d = x_l.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    E_l = w_gate.shape[0]
    x_f = x_l.reshape(-1, d)
    T = x_f.shape[0]
    gates, idx, aux = _route(router, x_f, cfg)
    # mask for choices owned by this rank
    local = (idx >= shard_id * E_l) & (idx < (shard_id + 1) * E_l)
    local_idx = jnp.where(local, idx - shard_id * E_l, 0)
    C = max(4, min(T * k, _capacity(T, cfg, 1)))
    buffers, inv = _local_group(x_f, jnp.where(local, gates, 0.0),
                                jnp.where(local, local_idx, E_l), E_l + 1, C)
    h = buffers[:E_l]
    y = _expert_ffn(w_gate, w_up, w_down, h, gmm_impl)
    y_full = jnp.concatenate(
        [y.reshape(E_l * C, d),
         jnp.zeros((C, d), y.dtype)]).reshape((E_l + 1) * C, d)
    flat = jnp.where((inv[:, None] >= 0) & local.reshape(-1)[:, None],
                     y_full[jnp.maximum(inv, 0)], 0.0)
    y_tok = (flat.reshape(T, k, d).astype(jnp.float32)
             * gates[..., None]).sum(axis=1)
    y_tok = jax.lax.psum(y_tok, axis) if n_shards > 1 else y_tok
    return y_tok.reshape(B_l, S_l, d).astype(x_l.dtype), aux / max(n_shards, 1)


def moe_apply_ep(p, x, cfg: ModelConfig, mesh, *, tp_axis: str = "model",
                 batch_axes=("pod", "data"), gmm_impl: str = "auto"
                 ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE. x [B,S,d] (global). Requires a mesh context."""
    n_shards = mesh.shape.get(tp_axis, 1) if mesh is not None else 1
    b_axes = tuple(a for a in batch_axes if mesh is not None
                   and a in mesh.shape)
    S = x.shape[1]
    decode = S < max(n_shards, 2)

    if mesh is None:
        y, aux = _moe_ep_local(
            x, _router_params(p), p["w_gate"], p["w_up"], p["w_down"],
            cfg=cfg, axis=tp_axis, n_shards=1, gmm_impl=gmm_impl)
        return y, aux

    from jax import shard_map

    all_axes = b_axes + ((tp_axis,) if n_shards > 1 else ())

    def _mean(aux):
        return jax.lax.pmean(aux, all_axes) if all_axes else aux

    if decode:
        def body(x_l, router, wg, wu, wd):
            sid = jax.lax.axis_index(tp_axis) if n_shards > 1 else 0
            y, aux = _moe_decode_local(
                x_l, router, wg, wu, wd, cfg=cfg, axis=tp_axis,
                n_shards=n_shards, shard_id=sid, gmm_impl=gmm_impl)
            return y, _mean(aux)
        x_spec = P(b_axes or None, None, None)
    else:
        def body(x_l, router, wg, wu, wd):
            y, aux = _moe_ep_local(
                x_l, router, wg, wu, wd, cfg=cfg, axis=tp_axis,
                n_shards=n_shards, gmm_impl=gmm_impl)
            return y, _mean(aux)
        x_spec = P(b_axes or None, tp_axis, None)   # sequence-split over TP

    w_spec = P(tp_axis, None, None)                 # experts live on TP ranks
    out = shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(), w_spec, w_spec, w_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, _router_params(p), p["w_gate"], p["w_up"], p["w_down"])
    return out


# ---------------------------------------------------------------------------
# Dropless path (every expert on one device)
# ---------------------------------------------------------------------------

def moe_apply_dropless(p, x, cfg: ModelConfig, *, gmm_impl: str = "auto"
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sort the (token, choice) rows by expert and run each expert's rows
    as one group of a ragged grouped matmul. x [B,S,d] -> (y, aux,
    tokens per expert [E])."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    xf = x.reshape(-1, d)
    gates, idx, aux = _route(p, xf, cfg)
    with jax.named_scope("moe_experts"):
        flat = idx.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
        rows = xf[order // k]                                  # [T*k, d]
        g = ops.ragged_gmm(rows, p["w_gate"], sizes, impl=gmm_impl)
        u = ops.ragged_gmm(rows, p["w_up"], sizes, impl=gmm_impl)
        h = (jax.nn.silu(g.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(x.dtype)
        y = ops.ragged_gmm(h, p["w_down"], sizes, impl=gmm_impl)
        y = jnp.zeros_like(y).at[order].set(y)                 # unsort
        out = (y.reshape(-1, k, d).astype(jnp.float32)
               * gates[..., None]).sum(axis=1)
    return out.reshape(B, S, d).astype(x.dtype), aux, sizes


def expert_load(idx, E: int) -> jax.Array:
    """Tokens routed to each expert: [E] int32."""
    return jnp.bincount(idx.reshape(-1), length=E).astype(jnp.int32)


@jax.named_scope("mlp")
def moe_apply(p, x, cfg: ModelConfig, *, impl: str = "auto", mesh=None,
              tp_axis: str = "model", batch_axes=("pod", "data"),
              gmm_impl: str = "auto"
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [B,S,d] -> (y, aux loss, tokens routed to each expert [E]).
    ``impl="auto"`` is ``"ep"`` under a mesh and ``"dropless"`` without."""
    if impl == "auto":
        impl = "ep" if mesh is not None else "dropless"
    if impl == "dropless":
        y, aux, load = moe_apply_dropless(p, x, cfg, gmm_impl=gmm_impl)
    else:
        if impl == "dense":
            y, aux = moe_apply_dense(p, x, cfg)
        elif impl == "ep":
            y, aux = moe_apply_ep(p, x, cfg, mesh, tp_axis=tp_axis,
                                  batch_axes=batch_axes, gmm_impl=gmm_impl)
        else:
            raise ValueError(f"unknown moe impl {impl!r}")
        load = expert_load(_route(p, x, cfg)[1], cfg.num_experts)
    if "shared" in p:
        with jax.named_scope("moe_shared"):
            y = y + layers.mlp_apply(p["shared"], x, "swiglu")
    return y, aux, load
