"""Flash attention (online softmax) Pallas TPU kernels, forward and backward.

``flash_attention_pallas`` is a ``jax.custom_vjp`` of three kernels:

* ``flash_attention`` (forward): grid (B, Hq, Sq/bq, Skv/bk), the KV axis
  innermost and sequential, carrying the running max / denominator /
  accumulator in VMEM scratch. Besides the output it writes the per-row
  log-sum-exp (f32), the residual the backward recomputes the softmax
  from.
* ``flash_attention_dkv``: grid (B, Hkv, Skv/bk, group, Sq/bq); for each
  KV tile a loop over the ``group`` query heads that share it and over
  their Q tiles, dK and dV accumulated in f32 VMEM scratch. GQA never
  repeats K/V in HBM: the Q-side index maps read head ``hkv * group + g``
  and the K/V side head ``h // group``.
* ``flash_attention_dq``: grid (B, Hq, Sq/bq, Skv/bk), for each Q tile a
  loop over KV tiles.

The MXU gets the operands in their own dtype (bf16 where the model
computes in bf16; P and dS are cast to it right before their dots) with
f32 accumulation; m, l, the log-sum-exp, ``di = rowsum(dO * O)`` and every
accumulator stay f32. Tiles that a causal mask or a sliding window hides
entirely do no work, and the index maps clamp the tile they would read
to the nearest needed one, so a skipped tile is not fetched either; only
tiles the band crosses build a mask.

Queries occupy the last Sq slots of the KV axis (the decode-offset
convention of ``ref.attention_ref``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b

# Scoped VMEM a kernel may plan for (v5e's default scoped limit); the
# block choice below keeps its estimate of the largest kernel under it.
VMEM_BUDGET = 16 * 2**20
_BLOCKS = (1024, 512, 256, 128)


def _vmem_bytes(bq: int, bk: int, d: int, itemsize: int) -> int:
    """Estimate of the backward's VMEM, the largest of the three kernels:
    double-buffered Q, dO, K, V tiles, the f32 accumulators, and two live
    [bq, bk] f32 temporaries (P beside dP, then dS)."""
    dp = -(-d // 128) * 128                       # lanes are 128 wide
    tiles = 2 * (2 * bq + 2 * bk) * dp * itemsize
    acc = 2 * max(bq, bk) * dp * 4
    return tiles + acc + 2 * bq * bk * 4


def _block(s: int) -> int:
    for b in _BLOCKS:
        if s % b == 0:
            return b
    return s                                      # one tile: the full axis


def pick_blocks(sq: int, skv: int, d: int, itemsize: int = 2
                ) -> Tuple[int, int]:
    """(block_q, block_kv) from the shapes: the largest tiles of at most
    1024 rows that divide the axes, the larger halved while the estimate
    exceeds ``VMEM_BUDGET``."""
    bq, bk = _block(sq), _block(skv)
    while (_vmem_bytes(bq, bk, d, itemsize) > VMEM_BUDGET
           and max(bq, bk) > 128):
        if bk >= bq:
            bk //= 2
        else:
            bq //= 2
    assert sq % bq == 0 and skv % bk == 0, (sq, skv, bq, bk)
    return bq, bk


# ---------------------------------------------------------------------------
# Which tiles the mask leaves anything in
# ---------------------------------------------------------------------------

def _kv_range(i, *, bq, bk, nk, q_off, causal, window):
    """First and last KV tile that Q tile ``i`` sees (traced ints)."""
    lo, hi = 0, nk - 1
    if causal:
        hi = jnp.minimum(hi, (i * bq + bq - 1 + q_off) // bk)
    if window:
        lo = jnp.maximum(i * bq + q_off - window + 1, 0) // bk
    return lo, hi


def _q_range(j, *, bq, bk, nq, q_off, causal, window):
    """First and last Q tile that sees KV tile ``j`` (traced ints)."""
    lo, hi = 0, nq - 1
    if causal:
        lo = jnp.maximum(j * bk - q_off, 0) // bq
    if window:
        hi = jnp.minimum(hi, jnp.maximum(
            j * bk + bk + window - 2 - q_off, 0) // bq)
    return lo, hi


def _unmasked(i, j, *, bq, bk, q_off, causal, window):
    """Every (query, key) pair of tile (i, j) is visible."""
    ok = jnp.asarray(True)
    if causal:
        ok &= i * bq + q_off >= j * bk + bk - 1
    if window:
        ok &= i * bq + bq - 1 + q_off - j * bk < window
    return ok


def _visible(i, j, shape, *, bq, bk, q_off, causal, window, transposed=False):
    """Mask of tile (i, j) in [bq, bk] layout, or [bk, bq] if transposed."""
    qa, ka = (1, 0) if transposed else (0, 1)
    q_pos = i * bq + q_off + jax.lax.broadcasted_iota(jnp.int32, shape, qa)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, shape, ka)
    m = jnp.ones(shape, bool)
    if causal:
        m &= q_pos >= k_pos
    if window:
        m &= q_pos - k_pos < window
    return m


def _run_tile(needed, unmasked, compute):
    """Run ``compute(masked)`` on a needed tile, building a mask only
    where the band crosses it."""
    pl.when(needed & unmasked)(lambda: compute(False))
    pl.when(needed & jnp.logical_not(unmasked))(lambda: compute(True))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, window, q_off, bq, bk, nk):
    i, j = pl.program_id(2), pl.program_id(3)
    geo = dict(bq=bq, bk=bk, q_off=q_off, causal=causal, window=window)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def compute(masked):
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_visible(i, j, s.shape, **geo), s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    lo, hi = _kv_range(i, nk=nk, **geo)
    _run_tile((j >= lo) & (j <= hi), _unmasked(i, j, **geo), compute)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l)).reshape(1, bq)


def _fwd(q, k, v, causal, window, scale, interpret):
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    bq, bk = pick_blocks(Sq, Skv, D, q.dtype.itemsize)
    nq, nk = Sq // bq, Skv // bk
    geo = dict(bq=bq, bk=bk, q_off=Skv - Sq, causal=causal, window=window)

    def kv_map(b, h, i, j):
        lo, hi = _kv_range(i, nk=nk, **geo)
        return b, h // group, jnp.clip(j, lo, hi), 0

    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, nk=nk, **geo),
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((B, Hq, 1, Sq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale, causal, window, q_off, bq, bk, nq,
                group):
    """One KV tile against every Q tile of its ``group`` query heads, in
    the transposed layout [bk, bq], where the log-sum-exp and ``di`` rows
    broadcast down the columns as they are stored."""
    j, g, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    geo = dict(bq=bq, bk=bk, q_off=q_off, causal=causal, window=window)

    @pl.when((g == 0) & (i == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def compute(masked):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        s = jax.lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_visible(i, j, s.shape, transposed=True, **geo),
                          s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0])                          # [bk, bq]
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0, 0])
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    lo, hi = _q_range(j, nq=nq, **geo)
    _run_tile((i >= lo) & (i <= hi), _unmasked(i, j, **geo), compute)

    @pl.when((g == group - 1) & (i == nq - 1))
    def _finish():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_scr,
               *, scale, causal, window, q_off, bq, bk, nk):
    i, j = pl.program_id(2), pl.program_id(3)
    geo = dict(bq=bq, bk=bk, q_off=q_off, causal=causal, window=window)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def compute(masked):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_visible(i, j, s.shape, **geo), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0].reshape(bq, 1))           # [bq, bk]
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0, 0].reshape(bq, 1))
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    lo, hi = _kv_range(i, nk=nk, **geo)
    _run_tile((j >= lo) & (j <= hi), _unmasked(i, j, **geo), compute)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_calls(q, k, v, do, lse, di, causal, window, scale, interpret):
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    bq, bk = pick_blocks(Sq, Skv, D, q.dtype.itemsize)
    nq, nk = Sq // bq, Skv // bk
    geo = dict(bq=bq, bk=bk, q_off=Skv - Sq, causal=causal, window=window)

    # dK, dV: grid (b, kv head, kv tile, head in group, q tile)
    def q_map(b, hk, j, g, i):
        lo, hi = _q_range(j, nq=nq, **geo)
        return b, hk * group + g, jnp.clip(i, lo, hi), 0

    def row_map(b, hk, j, g, i):
        lo, hi = _q_range(j, nq=nq, **geo)
        return b, hk * group + g, 0, jnp.clip(i, lo, hi)

    def kv_tile(b, hk, j, g, i):
        return b, hk, j, 0

    q_spec = pl.BlockSpec((1, 1, bq, D), q_map)
    kv_spec = pl.BlockSpec((1, 1, bk, D), kv_tile)
    row_spec = pl.BlockSpec((1, 1, 1, bq), row_map)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, nq=nq, group=group,
                          **geo),
        grid=(B, Hkv, nk, group, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, di)

    # dQ: grid (b, q head, q tile, kv tile)
    def kv_map(b, h, i, j):
        lo, hi = _kv_range(i, nk=nk, **geo)
        return b, h // group, jnp.clip(j, lo, hi), 0

    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D), kv_map)
    row_spec = pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, nk=nk, **geo),
        grid=(B, Hq, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, do, lse, di)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, window, scale, interpret):
    return _fwd(q, k, v, causal, window, scale, interpret)[0]


def _flash_fwd(q, k, v, causal, window, scale, interpret):
    o, lse = _fwd(q, k, v, causal, window, scale, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, scale, interpret, res, do):
    q, k, v, o, lse = res
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    return _bwd_calls(q, k, v, do.astype(q.dtype), lse, di[:, :, None, :],
                      causal, window, scale, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "scale", "interpret"))
def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int = 0, scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """q [B,Hq,Sq,D]; k,v [B,Hkv,Skv,D] -> [B,Hq,Sq,D], differentiable."""
    assert q.shape[1] % k.shape[1] == 0
    scale_ = (q.shape[-1] ** -0.5) if scale is None else scale
    return _flash(q, k, v, causal, window, scale_, interpret)
