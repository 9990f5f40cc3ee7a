"""Flash attention (online softmax) Pallas TPU kernel.

Tiling: grid = (B, Hq, Sq/block_q, Skv/block_kv); the KV-block dimension
is innermost and sequential ("arbitrary"), carrying the running max /
denominator / accumulator in VMEM scratch. Q blocks of (block_q, D) and
KV blocks of (block_kv, D) stream HBM->VMEM; with block_q = block_kv =
128 and D <= 128 the working set is ~4 x 128 x 128 x 4 B ≈ 256 KB —
MXU-aligned (128 lanes) and far under the v5e VMEM budget, leaving
headroom for double buffering.

Supports GQA (KV head index = Q head // group), causal masking with a
decode offset (queries occupy the last Sq slots of the KV axis), and
sliding-window banding. Fully-masked tiles short-circuit via pl.when.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, q_off: int,
                  block_q: int, block_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0) + q_off
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)           # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)           # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = jnp.ones_like(logits, dtype=bool)
        if causal:
            mask &= q_pos >= k_pos
        if window:
            mask &= (q_pos - k_pos) < window
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_scr[...][:, 0]
        l_prev = l_scr[...][:, 0]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1)
        acc = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new[:, None]
        l_scr[...] = l_new[:, None]
        acc_scr[...] = acc

    # tile-level skip: fully-masked tiles do no compute (causal future
    # tiles and, with a sliding window, tiles entirely left of the band)
    if causal or window:
        last_q = qi * block_q + q_off + block_q - 1
        needed = jnp.asarray(True)
        if causal:
            needed &= last_q >= ki * block_kv
        if window:
            first_q = qi * block_q + q_off
            needed &= (first_q - (ki * block_kv + block_kv - 1)) < window
        pl.when(needed)(compute)
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[...][:, 0]
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_kv",
                     "interpret"))
def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int = 0, scale: Optional[float] = None,
    block_q: int = 128, block_kv: int = 128, interpret: bool = False,
) -> jax.Array:
    """q [B,Hq,Sq,D]; k,v [B,Hkv,Skv,D] -> [B,Hq,Sq,D]."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale_ = (D ** -0.5) if scale is None else scale
    q_off = Skv - Sq

    block_q = min(block_q, Sq)
    while Sq % block_q:
        block_q //= 2
    block_kv = min(block_kv, Skv)
    while Skv % block_kv:
        block_kv //= 2

    grid = (B, Hq, Sq // block_q, Skv // block_kv)
    kernel = functools.partial(
        _flash_kernel, scale=scale_, causal=causal, window=window,
        q_off=q_off, block_q=block_q, block_kv=block_kv)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
