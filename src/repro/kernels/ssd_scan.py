"""Mamba2 SSD (state-space duality) chunked-scan Pallas kernel.

Grid = (B, H, S/chunk); the chunk dimension is innermost and sequential
("arbitrary"), carrying the inter-chunk SSM state [P, N] in VMEM
scratch. x and dt are laid out heads-before-sequence for the kernel;
the per-head A and D sit whole in SMEM. Per grid step the kernel loads
one chunk of x [Q, P], dt [Q, 1], B/C [Q, N], builds the intra-chunk
decay matrix L = exp(segsum(dt*A)) (lower-triangular [Q, Q]), and
fuses:

    y_intra = ((C B^T) * L) @ (x*dt)           -- MXU matmuls
    y_inter = (C * exp(cum)) @ state^T
    state  <- exp(total) * state + (x*dt * decay)^T @ B

With Q = 128, P = 64, N = 128 the VMEM working set is ~0.5 MB. All
matmul dims are multiples of 64/128 (MXU-aligned for the assigned
mamba2/hymba configs).

The dt*A product and exponentials stay in fp32 for stability; inputs
may be bf16.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref,
                y_ref, final_ref, state_scr, *, chunk: int):
    h = pl.program_id(1)
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)                # [Q, P]
    dt = dt_ref[0, 0].astype(jnp.float32)              # [Q, 1]
    A = a_ref[h]                                       # scalar (this head)
    Bm = b_ref[0].astype(jnp.float32)                  # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)                  # [Q, N]
    D = d_ref[h]

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = row >= col
    a = dt * A                                         # [Q, 1] log-decay
    # inclusive prefix sum as a lower-triangular matmul (Mosaic has no
    # cumsum lowering)
    cum = jax.lax.dot_general(tri.astype(jnp.float32), a,
                              (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)  # [Q, 1]
    total = cum[chunk - 1, 0]                          # scalar
    seg = cum - cum.T                                  # [Q, Q]
    L = jnp.where(tri, jnp.exp(seg), 0.0)

    xdt = x * dt                                       # [Q, P]
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    y_intra = jax.lax.dot_general(cb * L, xdt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    state = state_scr[...]                             # [P, N]
    c_dec = Cm * jnp.exp(cum)                          # [Q, N]
    y_inter = jax.lax.dot_general(c_dec, state, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    y_ref[0, 0] = (y_intra + y_inter + x * D).astype(y_ref.dtype)

    xs = xdt * jnp.exp(total - cum)                    # [Q, P]
    new_contrib = jax.lax.dot_general(xs, Bm, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
    state_scr[...] = state * jnp.exp(total) + new_contrib

    @pl.when(ci == nc - 1)
    def _finish():
        final_ref[0, 0] = state_scr[...].astype(final_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(
    x: jax.Array,      # [B, S, H, P]
    dt: jax.Array,     # [B, S, H]
    A: jax.Array,      # [H]
    Bm: jax.Array,     # [B, S, N]
    Cm: jax.Array,     # [B, S, N]
    D: jax.Array,      # [H]
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y [B,S,H,P], final_state [B,H,P,N]); matches ref.ssd_ref."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    nc = S // chunk

    # heads before sequence, so every VMEM block ends in (chunk, P),
    # (chunk, 1) or (chunk, N): the TPU tiling wants the last two block
    # dims divisible by (8, 128) or equal to the array's
    xh = jnp.swapaxes(x, 1, 2)                         # [B, H, S, P]
    dth = jnp.swapaxes(dt, 1, 2)[..., None]            # [B, H, S, 1]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    y, final = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            smem,
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            smem,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(xh, dth, A.astype(jnp.float32), Bm, Cm, D.astype(jnp.float32))
    return jnp.swapaxes(y, 1, 2), final
