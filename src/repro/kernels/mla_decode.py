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
split over T). The cache is every layer's, [L, B, C, T], read in place:
the layer and the position ``pos`` (one for the batch) are prefetched
scalars, and the layer axis of the cache's block is squeezed. Tiles
that hold no position before ``pos`` do no work, and their index map
clamps to the last needed tile, so they are not fetched either. The new
latent of position ``pos`` is not in the cache yet (the decode step
writes it after its layer scan) but comes as a row of its own; it joins
the running softmax as one more column after the last tile.
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


def _kernel(sc_ref, q_ref, c_ref, row_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale, r, bt, bb, nt):
    j = pl.program_id(1)
    pos = sc_ref[0]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def update(b, s, pv):
        """Fold scores s [H, n] and their values' weighted sum pv(p) in."""
        m_prev = m_scr[b]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[b] = alpha * l_scr[b] + p.sum(axis=-1, keepdims=True)
        acc_scr[b] = alpha * acc_scr[b] + pv(p)
        m_scr[b] = m_new

    @pl.when(j * bt < pos)
    def _tile():
        t = j * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        for b in range(bb):
            q, c = q_ref[b], c_ref[b]                   # [H, C], [C, bt]
            cv = c[:r]
            s = (jax.lax.dot_general(q[:, :r], cv, _NN,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(q[:, r:], c[r:], _NN,
                                       preferred_element_type=jnp.float32))
            s = jnp.where(t < pos, s * scale, NEG_INF)  # [H, bt]
            update(b, s, lambda p: jax.lax.dot_general(
                p.astype(c.dtype), cv, _NT,
                preferred_element_type=jnp.float32))

    @pl.when(j == nt - 1)
    def _finish():
        for b in range(bb):
            q = q_ref[b].astype(jnp.float32)            # [H, C]
            row = row_ref[b].astype(jnp.float32)        # [1, C]
            s = (q[:, :r] * row[:, :r]).sum(axis=-1, keepdims=True) \
                + (q[:, r:] * row[:, r:]).sum(axis=-1, keepdims=True)
            update(b, s * scale, lambda p: p * row[:, :r])
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("r", "scale", "interpret"))
def mla_decode_pallas(q: jax.Array, cache: jax.Array, layer, row: jax.Array,
                      pos: jax.Array, *, r: int, scale: float,
                      interpret: bool = False) -> jax.Array:
    """q [B, H, C] (absorbed query: latent part then rope part), cache
    [L, B, C, T] every layer's latents, ``layer`` the one attended, row
    [B, C, 1] the latent of position ``pos``, the last one attended ->
    [B, H, r], the attention-weighted latent."""
    B, H, C = q.shape
    T = cache.shape[3]
    bt = _block(T, (512, 256, 128))
    bb = _block(B, (8, 4, 2, 1))
    nt = T // bt

    def c_map(i, j, sc):
        return sc[1], i, 0, jnp.minimum(j, sc[0] // bt)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B // bb, nt),
        in_specs=[pl.BlockSpec((bb, H, C), lambda i, j, sc: (i, 0, 0)),
                  pl.BlockSpec((pl.Squeezed(), bb, C, bt), c_map),
                  pl.BlockSpec((bb, 1, C), lambda i, j, sc: (i, 0, 0))],
        out_specs=pl.BlockSpec((bb, H, r), lambda i, j, sc: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((bb, H, 1), jnp.float32),
                        pltpu.VMEM((bb, H, 1), jnp.float32),
                        pltpu.VMEM((bb, H, r), jnp.float32)])
    scalars = jnp.stack([jnp.asarray(pos, jnp.int32),
                         jnp.asarray(layer, jnp.int32)])
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, r=r, bt=bt, bb=bb, nt=nt),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_decode",
    )(scalars, q, cache, jnp.reshape(row, (B, 1, C)))


def mla_decode_xla(q: jax.Array, cache: jax.Array, layer, row: jax.Array,
                   pos: jax.Array, *, r: int, scale: float) -> jax.Array:
    """The same arithmetic as two einsums over the layer's whole cache
    (bf16 operands, f32 accumulation): scores, then the weighted latent.
    The layer is read in place, the new row taking position ``pos``
    through a select that fuses into the einsums."""
    c = jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
    t = jnp.arange(c.shape[2])
    c = jnp.where(t == pos, row, c)
    s = jnp.einsum("bhc,bct->bht", q, c,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(t[None, None, :] <= pos, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bht,bct->bhc", p.astype(c.dtype), c,
                   preferred_element_type=jnp.float32)
    return o[..., :r].astype(q.dtype)
