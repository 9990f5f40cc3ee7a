"""Weights of the latent-attention MoE decoder, drawn from a run's seed.

The program under test is handed these; the reference draws the same
ones again after the window. The layout is the checkpoint layout that
``reference/mla_moe_lm.py`` writes out (``shapes``). Weights are drawn
in the configuration's parameter dtype (bf16) leaf by leaf, so that no
float32 copy of a 3-billion-parameter model is ever made; the router and
its score-correction bias are float32, as the model computes its scores.

Spreads: fan-in scaling for every projection, norm scales 1 + N(0,
0.1^2) (so that a scale applied to the wrong tensor shows), the
score-correction bias N(0, ``BIAS_STD``^2), and the input embedding
d^-1/2 times the traffic's ``embed_scale``.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from bench import weights

BIAS_STD = 0.05
F32_LEAVES = ("router", "router_bias")


def shapes(m: Dict) -> Dict:
    """name -> shape of every parameter."""
    d, H, V = m["d_model"], m["heads"], m["vocab"]
    r, rp = m["kv_lora_rank"], m["qk_rope_head_dim"]
    nope, vd = m["qk_nope_head_dim"], m["v_head_dim"]
    E, ff = m["experts"], m["moe_d_ff"]

    def attn(L):
        a = {}
        if m["q_lora_rank"]:
            qr = m["q_lora_rank"]
            a.update(wq_a=(L, d, qr), q_a_norm=(L, qr),
                     wq_b=(L, qr, H, nope + rp))
        else:
            a["wq"] = (L, d, H, nope + rp)
        a.update(wkv_a=(L, d, r + rp), kv_a_norm=(L, r),
                 wk_b=(L, r, H, nope), wv_b=(L, r, H, vd), wo=(L, H, vd, d))
        return a

    def mlp(L, width):
        return {"w_gate": (L, d, width), "w_up": (L, d, width),
                "w_down": (L, width, d)}

    Ld = m["dense_layers"]
    Lm = m["layers"] - Ld
    out = {"embed": (V, d), "unembed": (V, d), "final_norm": (d,),
           "layers": {"norm1": (Lm, d), "norm2": (Lm, d), "attn": attn(Lm),
                      "moe": {"router": (Lm, d, E), "router_bias": (Lm, E),
                              "w_gate": (Lm, E, d, ff),
                              "w_up": (Lm, E, d, ff),
                              "w_down": (Lm, E, ff, d),
                              "shared": mlp(Lm, m["shared_experts"] * ff)}}}
    if Ld:
        out["dense_layers"] = {"norm1": (Ld, d), "norm2": (Ld, d),
                               "attn": attn(Ld), "mlp": mlp(Ld, m["d_ff"])}
    return out


def _std(name: str, shape) -> float:
    if name in ("embed", "unembed"):
        return shape[1] ** -0.5
    if name == "router_bias":
        return BIAS_STD
    if name == "wo":
        return (shape[1] * shape[2]) ** -0.5
    if name in ("w_gate", "w_up", "w_down") and len(shape) == 4:
        return shape[2] ** -0.5                    # [L, E, fan_in, out]
    return shape[1] ** -0.5                        # [L, fan_in, ...]


@functools.lru_cache(maxsize=None)
def _leaf(shape: tuple, dtype: str, norm: bool, std: float):
    def draw(key):
        z = jax.random.normal(key, shape, dtype)
        return (1.0 + 0.1 * z) if norm else z * jnp.asarray(std, dtype)
    return jax.jit(draw)


def make_params(seed: int, m: Dict, embed_scale: float = 1.0,
                dtype: str = "bfloat16") -> Dict:
    """Every parameter, on the default device, leaf by leaf."""
    key = weights._key(seed)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(m), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shp) in enumerate(flat):
        name = path[-1].key
        norm = "norm" in name
        std = 0.0 if norm else _std(name, shp) * (
            embed_scale if name == "embed" else 1.0)
        dt = "float32" if name in F32_LEAVES else dtype
        out.append(_leaf(tuple(shp), dt, norm, float(std))(
            jax.random.fold_in(key, i)))
    return jax.tree_util.tree_unflatten(treedef, out)
