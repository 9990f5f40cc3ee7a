"""Grouped (per-expert) matmul Pallas kernel — fixed-capacity layout.

After the EP all_to_all, each device holds its local experts' token
buffers lhs [E_local, C, K] and weights rhs [E_local, K, N]. The kernel
is a batched tiled matmul: grid = (E, C/bc, N/bn, K/bk) with the K
dimension innermost/sequential accumulating into a VMEM fp32 scratch
tile of (bc, bn). Tiles default to 128x128(x512 K-step): MXU-aligned,
~0.6 MB working set — double-bufferable.

(A megablox-style *ragged* layout would avoid padding to capacity; the
capacity layout was chosen because it keeps all shapes static across
iterations — required for the fixed-shape pjit dry-run — and matches
the GShard-family dispatch in models/moe.py.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(lhs_ref, rhs_ref, out_ref, acc_scr):
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        lhs_ref[0], rhs_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        out_ref[0] = acc_scr[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "block_n", "block_k",
                                             "interpret"))
def moe_gmm_pallas(lhs: jax.Array, rhs: jax.Array, *,
                   block_c: int = 128, block_n: int = 128, block_k: int = 512,
                   interpret: bool = False) -> jax.Array:
    """lhs [E, C, K] @ rhs [E, K, N] -> [E, C, N] (fp32 accumulation)."""
    E, C, K = lhs.shape
    _, _, N = rhs.shape

    def fit(blk, dim):
        blk = min(blk, dim)
        while dim % blk:
            blk //= 2
        return blk

    bc, bn, bk = fit(block_c, C), fit(block_n, N), fit(block_k, K)
    grid = (E, C // bc, N // bn, K // bk)

    return pl.pallas_call(
        _gmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bk), lambda e, c, n, k: (e, c, k)),
            pl.BlockSpec((1, bk, bn), lambda e, c, n, k: (e, k, n)),
        ],
        out_specs=pl.BlockSpec((1, bc, bn), lambda e, c, n, k: (e, c, n)),
        out_shape=jax.ShapeDtypeStruct((E, C, N), lhs.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(lhs, rhs)
