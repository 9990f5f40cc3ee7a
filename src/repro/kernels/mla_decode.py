"""Absorbed latent-attention decode (MLA) as a Pallas TPU kernel.

After ``W_UK`` is folded into the query and ``W_UV`` into the output, a
decode step of latent attention reads one shared key and value per
position for all heads: the cached latent ``[c_kv | k_pe]``, of width
``C = r + rope``. Scores are ``q[:, :r] . c_kv + q[:, r:] . k_pe``, and
the value is ``c_kv`` itself, so every head reads the same cache
entries and the cache is read once a step, in its own dtype, with f32
accumulation.

The cache is laid out [B, C, T], positions minor: 576 = 512 + 64 is not
a multiple of the 128 lanes, so a [T, 576] row layout would be padded
to 640 lanes (or transposed by XLA around every call); 7936 positions
and 576 sublanes tile exactly.

Grid (B / bb, T / bt): ``bb`` batch rows per step, the T axis innermost
and sequential, carrying the running max, denominator and the [H, r]
accumulator of each row in VMEM scratch (flash decoding without the
split over T). The position ``pos`` (one for the batch) is a prefetched
scalar: tiles past it do no work, and their index map clamps to the last
needed tile, so they are not fetched either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _block(n: int, choices) -> int:
    for b in choices:
        if n % b == 0:
            return b
    return n


def _kernel(pos_ref, q_ref, c_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, r, bt, bb, nt):
    j = pl.program_id(1)
    pos = pos_ref[0]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * bt <= pos)
    def _tile():
        t = j * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        for b in range(bb):
            q, c = q_ref[b], c_ref[b]                   # [H, C], [C, bt]
            cv = c[:r]
            s = (jax.lax.dot_general(q[:, :r], cv, _NN,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(q[:, r:], c[r:], _NN,
                                       preferred_element_type=jnp.float32))
            s = jnp.where(t <= pos, s * scale, NEG_INF)  # [H, bt]
            m_prev = m_scr[b]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[b] = alpha * l_scr[b] + p.sum(axis=-1, keepdims=True)
            acc_scr[b] = alpha * acc_scr[b] + jax.lax.dot_general(
                p.astype(c.dtype), cv, _NT,
                preferred_element_type=jnp.float32)
            m_scr[b] = m_new

    @pl.when(j == nt - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("r", "scale", "interpret"))
def mla_decode_pallas(q: jax.Array, cache: jax.Array, pos: jax.Array, *,
                      r: int, scale: float,
                      interpret: bool = False) -> jax.Array:
    """q [B, H, C] (absorbed query: latent part then rope part), cache
    [B, C, T] latents, pos scalar: the last position attended ->
    [B, H, r], the attention-weighted latent."""
    B, H, C = q.shape
    T = cache.shape[2]
    bt = _block(T, (512, 256, 128))
    bb = _block(B, (8, 4, 2, 1))
    nt = T // bt

    def c_map(i, j, pos_ref):
        return i, 0, jnp.minimum(j, pos_ref[0] // bt)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B // bb, nt),
        in_specs=[pl.BlockSpec((bb, H, C), lambda i, j, p: (i, 0, 0)),
                  pl.BlockSpec((bb, C, bt), c_map)],
        out_specs=pl.BlockSpec((bb, H, r), lambda i, j, p: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((bb, H, 1), jnp.float32),
                        pltpu.VMEM((bb, H, 1), jnp.float32),
                        pltpu.VMEM((bb, H, r), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, r=r, bt=bt, bb=bb, nt=nt),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_decode",
    )(jnp.reshape(pos, (1,)).astype(jnp.int32), q, cache)


def mla_decode_xla(q: jax.Array, cache: jax.Array, pos: jax.Array, *,
                   r: int, scale: float) -> jax.Array:
    """The same arithmetic as two einsums over the whole cache (bf16
    operands, f32 accumulation): scores, then the weighted latent."""
    s = jnp.einsum("bhc,bct->bht", q, cache,
                   preferred_element_type=jnp.float32) * scale
    t = jnp.arange(cache.shape[2])
    s = jnp.where(t[None, None, :] <= pos, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bht,bct->bhc", p.astype(cache.dtype), cache,
                   preferred_element_type=jnp.float32)
    return o[..., :r].astype(q.dtype)
