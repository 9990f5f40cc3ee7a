"""Efficient pure-XLA implementations of the kernel ops.

The ``impl`` switch in ops.py takes these off a TPU, and on a TPU where
a Pallas kernel cannot take the call (attention with a traced window, a
prefix, a dynamic KV length or a sharded mesh). Numerics match ref.py
(tested).

Causal attention is ``attention_blockwise``: a lax.scan over KV blocks
with masking, which computes the full Sq x Skv rectangle.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _static_zero(window) -> bool:
    return isinstance(window, int) and window == 0


def _mask_block(q_pos, k_pos, causal: bool, window, prefix: int = 0):
    """window may be a traced int32 scalar (0 => full attention);
    positions < prefix are always visible (e.g. hymba meta tokens)."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if not _static_zero(window):
        w = jnp.asarray(window)
        band = (q_pos[:, None] - k_pos[None, :]) < w
        if prefix:
            band |= k_pos[None, :] < prefix
        m &= band | (w <= 0)
    return m


def _online_update(carry, kblk, vblk, q, q_pos, k_pos, scale, causal, window,
                   kv_len=None, prefix=0):
    m_prev, l_prev, acc = carry
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kblk) * scale   # fp32
    mask = _mask_block(q_pos, k_pos, causal, window, prefix)[None, None]
    if kv_len is not None:
        mask = mask & (k_pos[None, None, None, :] < kv_len[:, None, None, None])
    logits = jnp.where(mask, logits, NEG_INF)
    m_new = jnp.maximum(m_prev, logits.max(axis=-1))
    p = jnp.exp(logits - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1)
    acc = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vblk)
    return m_new, l_new, acc


def attention_blockwise(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int = 0, scale: Optional[float] = None,
    block_kv: int = 512, kv_len: Optional[jax.Array] = None, prefix: int = 0,
    q_start=None,
) -> jax.Array:
    """Online-softmax attention. q [B,Hq,Sq,D]; k,v [B,Hkv,Skv,D].

    ``q_start`` overrides the queries' absolute start position (default
    Skv - Sq, the decode-offset convention; may be traced)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale
    qf = q.astype(jnp.float32)
    kf = jnp.repeat(k, group, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, group, axis=1).astype(jnp.float32)

    block_kv = min(block_kv, Skv)
    while Skv % block_kv:
        block_kv //= 2
    nkv = Skv // block_kv
    q_off = (Skv - Sq) if q_start is None else q_start
    ks = kf.reshape(B, Hq, nkv, block_kv, D).transpose(2, 0, 1, 3, 4)
    vs = vf.reshape(B, Hq, nkv, block_kv, D).transpose(2, 0, 1, 3, 4)
    q_pos = jnp.arange(Sq) + q_off

    def body(carry, blk):
        kblk, vblk, j = blk
        k_pos = j * block_kv + jnp.arange(block_kv)
        return _online_update(carry, kblk, vblk, qf, q_pos, k_pos, scale,
                              causal, window, kv_len, prefix), None

    init = (jnp.full((B, Hq, Sq), NEG_INF, jnp.float32),
            jnp.zeros((B, Hq, Sq), jnp.float32),
            jnp.zeros((B, Hq, Sq, D), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, (ks, vs, jnp.arange(nkv)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunked SSD (Mamba2 state-space duality)
# ---------------------------------------------------------------------------

def _segsum(a: jax.Array) -> jax.Array:
    """a [..., Q] -> L [..., Q, Q] with L[i,j] = sum_{j<t<=i} a_t (i>=j)."""
    Q = a.shape[-1]
    cum = jnp.cumsum(a, axis=-1)
    seg = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), dtype=bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(
    x: jax.Array,      # [B, S, H, P]
    dt: jax.Array,     # [B, S, H]
    A: jax.Array,      # [H]
    Bm: jax.Array,     # [B, S, N]
    Cm: jax.Array,     # [B, S, N]
    D: Optional[jax.Array] = None,
    init_state: Optional[jax.Array] = None,   # [B, H, P, N]
    chunk: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD: O(S*chunk) intra matmuls + O(S/chunk) state scan.

    Returns (y [B,S,H,P], final_state [B,H,P,N]). Matches ssd_ref.
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    nc = S // chunk

    xf = x.astype(jnp.float32).reshape(Bsz, nc, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bsz, nc, chunk, H)
    Bf = Bm.astype(jnp.float32).reshape(Bsz, nc, chunk, N)
    Cf = Cm.astype(jnp.float32).reshape(Bsz, nc, chunk, N)
    Af = A.astype(jnp.float32)

    a = dtf * Af[None, None, None, :]               # [B,nc,Q,H] log-decay
    a = jnp.moveaxis(a, -1, 2)                      # [B,nc,H,Q]
    cum = jnp.cumsum(a, axis=-1)                    # [B,nc,H,Q]
    total = cum[..., -1]                            # [B,nc,H]

    L = jnp.exp(_segsum(a))                         # [B,nc,H,Q,Q]
    xdt = xf * dtf[..., None]                       # [B,nc,Q,H,P]

    # intra-chunk: Y[i] = sum_{j<=i} (C_i . B_j) L[i,j] xdt_j
    cb = jnp.einsum("bcin,bcjn->bcij", Cf, Bf)      # [B,nc,Q,Q]
    scores = cb[:, :, None] * L                     # [B,nc,H,Q,Q]
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", scores, xdt)

    # chunk states: S_c = sum_j exp(total - cum_j) B_j xdt_j  -> [B,nc,H,P,N]
    decay_state = jnp.exp(total[..., None] - cum)   # [B,nc,H,Q]
    states = jnp.einsum("bchj,bcjn,bcjhp->bchpn", decay_state, Bf, xdt)

    # inter-chunk recurrence over nc
    state0 = (jnp.zeros((Bsz, H, P, N), jnp.float32) if init_state is None
              else init_state.astype(jnp.float32))

    def scan_fn(prev, inp):
        st, tot = inp                               # [B,H,P,N], [B,H]
        new = st + prev * jnp.exp(tot)[..., None, None]
        return new, prev

    final, prevs = jax.lax.scan(scan_fn, state0,
                                (jnp.moveaxis(states, 1, 0),
                                 jnp.moveaxis(total, 1, 0)))
    prev_states = jnp.moveaxis(prevs, 0, 1)         # state entering chunk c

    # inter-chunk contribution: C_i exp(cum_i) S_prev
    decay_out = jnp.exp(cum)                        # [B,nc,H,Q]
    y_inter = jnp.einsum("bcin,bchi,bchpn->bcihp", Cf, decay_out, prev_states)

    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    if D is not None:
        y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), final


# ---------------------------------------------------------------------------
# Grouped matmul (fixed-capacity expert layout)
# ---------------------------------------------------------------------------

def gmm(lhs: jax.Array, rhs: jax.Array) -> jax.Array:
    """[E,C,K] x [E,K,N] -> [E,C,N] with fp32 accumulation."""
    return jax.lax.dot_general(
        lhs, rhs, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(lhs.dtype)
