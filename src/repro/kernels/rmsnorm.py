"""Fused RMSNorm Pallas kernel.

Rows are tiled into VMEM blocks of (block_rows, D); the whole feature
dim stays resident (D <= 8192 => <= 4 MB fp32 per block, well inside the
~16 MB v5e VMEM budget together with the output tile). Reduction and
rescale happen in one pass — one HBM read + one write per element
vs. the unfused XLA chain.

The backward pass is declared as a ``jax.custom_vjp`` in plain jnp
(fp32, recomputing the inverse RMS from the saved input), which XLA
fuses into two passes over ``x`` and ``g``; the training step
differentiates through it on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)[None, :]).astype(o_ref.dtype)


def _forward(x2, w, eps: float, block_rows: int, interpret: bool):
    rows, d = x2.shape
    block_rows = min(block_rows, rows)
    while rows % block_rows:
        block_rows //= 2
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda r: (r, 0)),
            pl.BlockSpec((d,), lambda r: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x2.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rmsnorm(x2, w, eps, block_rows, interpret):
    return _forward(x2, w, eps, block_rows, interpret)


def _rmsnorm_fwd(x2, w, eps, block_rows, interpret):
    return _forward(x2, w, eps, block_rows, interpret), (x2, w)


def _rmsnorm_bwd(eps, block_rows, interpret, res, g):
    x2, w = res
    x = x2.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    gw = g.astype(jnp.float32) * w.astype(jnp.float32)[None, :]
    dx = r * gw - x * (r ** 3) * jnp.mean(gw * x, axis=-1, keepdims=True)
    dw = jnp.sum(g.astype(jnp.float32) * x * r, axis=0)
    return dx.astype(x2.dtype), dw.astype(w.dtype)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_pallas(x: jax.Array, w: jax.Array, *, eps: float = 1e-5,
                   block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """x [..., D], w [D] -> normalized [..., D]."""
    d = x.shape[-1]
    out = _rmsnorm(x.reshape(-1, d), w, eps, block_rows, interpret)
    return out.reshape(x.shape)
