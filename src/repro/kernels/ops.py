"""Dispatch layer for the kernel ops.

Model code calls these; ``impl`` selects the backend:

* ``"ref"``      — pure-jnp oracle (tests; decode attention)
* ``"pallas"``   — Pallas TPU kernel; raises on any other backend (tests
                   call the kernels themselves with ``interpret=True``)
* ``"auto"``     — pallas on a TPU, the XLA path elsewhere; for
                   attention, the Pallas kernel only where
                   ``flash_qualifies`` says it can take the call
* ``"xla"``      — the pure-XLA path of ``kernels/xla.py``; attention
                   calls it ``"blockwise"`` and refuses any other name

This is where ``"auto"`` is decided, from what the call can observe: the
backend, and for attention the shapes and the mesh. ``ModelCtx`` holds a
caller's override (``models/blocks.py``).

``rmsnorm_pallas`` and ``flash_attention_pallas`` have VJPs.
``ssd_scan_pallas``, ``moe_gmm_pallas`` and ``mla_decode_pallas`` are
forward-only, so the training step (``ModelCtx``'s defaults) takes the
``"xla"`` paths of ``ssd`` and ``gmm``; a gradient taken through their
``"pallas"`` paths fails in JAX's linearization. ``ragged_gmm``'s Pallas
path is megablox's ``gmm``, which has a VJP.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import xla as _xla
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mla_decode import mla_decode_pallas, mla_decode_xla
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


def _require_tpu() -> None:
    """A kernel behind ``impl="pallas"`` runs compiled on a TPU and is
    never dropped into the interpreter behind the caller's back."""
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"impl='pallas' needs a TPU backend, got "
            f"{jax.default_backend()!r}; call the kernel with "
            f"interpret=True to run it in the Pallas interpreter")


def _auto(impl: str) -> str:
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def flash_qualifies(q: jax.Array, k: jax.Array, *, window=0, prefix: int = 0,
                    kv_len=None, q_start=None, mesh=None) -> bool:
    """The flash kernel can take this attention call: a TPU backend, an
    unsharded call (no mesh, or a mesh of one device), a static window,
    no always-visible prefix, no dynamic KV length or query offset, and
    sequence and head sizes that tile (192 is latent attention's q/k
    width)."""
    Sq, D = q.shape[2], q.shape[3]
    Skv = k.shape[2]
    return (jax.default_backend() == "tpu"
            and (mesh is None or mesh.size == 1)
            and isinstance(window, int)
            and prefix == 0 and kv_len is None and q_start is None
            and Sq % 128 == 0 and Skv % 128 == 0 and D in (64, 128, 192))


# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-5,
            impl: str = "auto") -> jax.Array:
    impl = _auto(impl)
    if impl == "pallas":
        _require_tpu()
        return rmsnorm_pallas(x, w, eps=eps)
    return _ref.rmsnorm_ref(x, w, eps)   # XLA fuses this fine


def attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int = 0, scale: Optional[float] = None,
    impl: str = "auto", block_kv: int = 512,
    kv_len: Optional[jax.Array] = None, prefix: int = 0,
    q_start=None, mesh=None,
) -> jax.Array:
    """q [B,Hq,Sq,D]; k,v [B,Hkv,Skv,D]. ``kv_len`` masks a dynamic KV
    prefix (decode); only ref/blockwise support it. ``window`` may be a
    traced scalar for the xla paths (0 => full); ``prefix`` keys are
    always visible (hymba meta tokens); ``q_start`` overrides the queries'
    absolute start (blockwise only). ``mesh`` is the mesh the call runs
    under, which ``"auto"`` reads."""
    if impl == "auto":
        impl = "pallas" if flash_qualifies(
            q, k, window=window, prefix=prefix, kv_len=kv_len,
            q_start=q_start, mesh=mesh) else "blockwise"
    if impl == "pallas":
        assert kv_len is None and q_start is None, \
            "pallas path is for static-length attention"
        assert prefix == 0 and isinstance(window, int)
        _require_tpu()
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      scale=scale)
    if impl == "ref":
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  scale=scale, kv_len=kv_len, prefix=prefix)
    if impl != "blockwise":
        raise ValueError(f"unknown attention impl {impl!r}")
    return _xla.attention_blockwise(q, k, v, causal=causal, window=window,
                                    scale=scale, block_kv=block_kv,
                                    kv_len=kv_len, prefix=prefix,
                                    q_start=q_start)


def ssd(
    x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array, Cm: jax.Array,
    D: Optional[jax.Array] = None, *,
    init_state: Optional[jax.Array] = None, chunk: int = 128,
    impl: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    impl = _auto(impl)
    if impl == "pallas":
        assert init_state is None, "pallas ssd starts from zero state"
        _require_tpu()
        Dk = D if D is not None else jnp.zeros(A.shape, jnp.float32)
        return ssd_scan_pallas(x, dt, A, Bm, Cm, Dk, chunk=chunk)
    if impl == "ref":
        return _ref.ssd_ref(x, dt, A, Bm, Cm, D, init_state)
    return _xla.ssd_chunked(x, dt, A, Bm, Cm, D, init_state, chunk)


def ssd_decode(x, dt, A, Bm, Cm, state, D=None):
    """Single-token recurrent step (always XLA; O(1) work)."""
    return _ref.ssd_decode_ref(x, dt, A, Bm, Cm, state, D)


def gmm(lhs: jax.Array, rhs: jax.Array, *, impl: str = "auto") -> jax.Array:
    """Grouped matmul [E,C,K] x [E,K,N] -> [E,C,N]."""
    impl = _auto(impl)
    if impl == "pallas":
        _require_tpu()
        return moe_gmm_pallas(lhs, rhs)
    if impl == "ref":
        return _ref.gmm_ref(lhs, rhs)
    return _xla.gmm(lhs, rhs)


def ragged_gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
               impl: str = "auto") -> jax.Array:
    """Dropless grouped matmul: rows [m, K] sorted by group, rhs
    [G, K, N], ``group_sizes`` [G] int32 summing to at most m -> [m, N]
    in ``lhs``'s dtype (f32 accumulation); rows past the groups' sum are
    not defined. The Pallas path is megablox's ``gmm`` with ``m`` padded
    to its row tile."""
    impl = _auto(impl)
    if impl == "pallas":
        _require_tpu()
        from jax.experimental.pallas.ops.tpu.megablox import gmm as mb_gmm
        m, k = lhs.shape
        n = rhs.shape[2]
        tm = 128 if m <= 4096 else 512
        pad = -m % tm
        out = mb_gmm(jnp.pad(lhs, ((0, pad), (0, 0))), rhs, group_sizes,
                     preferred_element_type=lhs.dtype,
                     tiling=(tm, min(k, 2048 if tm == 128 else 512),
                             min(n, 512)))
        return out[:m]
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)


def latent_decode_attention(q: jax.Array, cache: jax.Array, layer,
                            row: jax.Array, pos: jax.Array, *, r: int,
                            scale: float, impl: str = "auto") -> jax.Array:
    """Absorbed latent-attention decode: q [B, H, C] over layer ``layer``
    of the stacked cache [L, B, C, T], read in place, with the new row
    [B, C, 1] at ``pos``; positions <= ``pos`` attended -> [B, H, r] (see
    ``kernels/mla_decode.py``)."""
    impl = _auto(impl)
    if impl == "pallas":
        _require_tpu()
        return mla_decode_pallas(q, cache, layer, row, pos, r=r, scale=scale)
    return mla_decode_xla(q, cache, layer, row, pos, r=r, scale=scale)
