"""Shared neural-net layers: init helpers, norms, rope, MLPs, embeddings.

Parameters are plain pytrees (nested dicts of jnp arrays); every init
function has a ``*_axes`` twin returning the matching tree of logical
axis-name tuples used by sharding/specs.py.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops


def trunc_normal(key, shape, scale: float, dtype) -> jax.Array:
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def dense_init(key, d_in: int, d_out_shape: Tuple[int, ...], dtype) -> jax.Array:
    """Fan-in scaled init for a projection [d_in, *d_out_shape]."""
    return trunc_normal(key, (d_in, *d_out_shape), d_in ** -0.5, dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5,
            impl: str = "xla") -> jax.Array:
    return ops.rmsnorm(x, w, eps=eps, impl=impl)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x [..., S, D] (D even), positions [S] or broadcastable."""
    D = x.shape[-1]
    freqs = rope_freqs(D, theta)                        # [D/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [S, D/2]
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, ff: int, kind: str, dtype) -> Dict[str, jax.Array]:
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {
            "w_gate": dense_init(ks[0], d, (ff,), dtype),
            "w_up": dense_init(ks[1], d, (ff,), dtype),
            "w_down": trunc_normal(ks[2], (ff, d), ff ** -0.5, dtype),
        }
    return {   # gelu (whisper-style, no biases)
        "w_up": dense_init(ks[0], d, (ff,), dtype),
        "w_down": trunc_normal(ks[1], (ff, d), ff ** -0.5, dtype),
    }


def mlp_axes(kind: str) -> Dict[str, Tuple[str, ...]]:
    if kind == "swiglu":
        return {
            "w_gate": ("embed", "ffn"),
            "w_up": ("embed", "ffn"),
            "w_down": ("ffn", "embed"),
        }
    return {"w_up": ("embed", "ffn"), "w_down": ("ffn", "embed")}


@jax.named_scope("mlp")
def mlp_apply(p: Dict[str, jax.Array], x: jax.Array, kind: str) -> jax.Array:
    if kind == "swiglu":
        g = jnp.einsum("...d,df->...f", x, p["w_gate"])
        u = jnp.einsum("...d,df->...f", x, p["w_up"])
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, p["w_up"]))
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    # std d^-0.5: lookup (scaled by sqrt(d)) has unit variance and the
    # tied/untied unembed produces O(1) logits at init.
    return trunc_normal(key, (vocab, d), d ** -0.5, dtype)


def embed_lookup(table: jax.Array, ids: jax.Array, d: int) -> jax.Array:
    out = jnp.take(table, ids, axis=0)
    return out * (d ** 0.5) / jnp.asarray(1.0, out.dtype)  # scaled embed


def unembed(x: jax.Array, table: jax.Array) -> jax.Array:
    """x [..., d] @ table^T [V, d] -> logits fp32, the operands in the
    wider of their two dtypes (a bf16 table is never copied to f32)."""
    dt = jnp.promote_types(x.dtype, table.dtype)
    return jnp.einsum("...d,vd->...v", x.astype(dt), table.astype(dt),
                      preferred_element_type=jnp.float32)
