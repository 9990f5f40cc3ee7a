"""Dispatch layer for the kernel ops.

Model code calls these; ``impl`` selects the backend:

* ``"ref"``      — pure-jnp oracle (tests)
* ``"xla"``      — efficient pure-XLA path (what the CPU dry-run lowers;
                   the baseline on real hardware too)
* ``"pallas"``   — Pallas TPU kernel; raises on any other backend (tests
                   call the kernels themselves with ``interpret=True``)
* ``"auto"``     — xla on CPU, pallas on TPU

Only ``rmsnorm_pallas`` has a VJP. ``ssd_scan_pallas`` and
``moe_gmm_pallas`` are forward-only, so the training step
(``train.step.build_ctx``) routes ``ssd`` and ``gmm`` to ``"xla"``; a
gradient taken through their ``"pallas"`` paths fails in JAX's
linearization.

Attention additionally supports the schedule variants of the XLA path
(``blockwise`` / ``blockwise_tri`` / ``dense``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import xla as _xla
from repro.kernels.flash_attention import flash_attention_pallas
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
) -> jax.Array:
    """q [B,Hq,Sq,D]; k,v [B,Hkv,Skv,D]. ``kv_len`` masks a dynamic KV
    prefix (decode); only dense/blockwise support it. ``window`` may be a
    traced scalar for the xla paths (0 => full); ``prefix`` keys are
    always visible (hymba meta tokens)."""
    impl = _auto(impl)
    if impl == "pallas":
        assert kv_len is None, "pallas path is for static-length attention"
        assert prefix == 0 and isinstance(window, int)
        _require_tpu()
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      scale=scale)
    if impl == "ref":
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  scale=scale, kv_len=kv_len, prefix=prefix)
    if impl == "dense":
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  scale=scale, kv_len=kv_len, prefix=prefix)
    if (impl == "blockwise_tri" and isinstance(window, int)
            and (prefix == 0 or window == 0)):
        return _xla.attention_blockwise(q, k, v, causal=causal, window=window,
                                        scale=scale, block_kv=block_kv,
                                        triangular=True, prefix=prefix)
    # default xla / blockwise (also blockwise_tri fallback for traced window)
    return _xla.attention_blockwise(q, k, v, causal=causal, window=window,
                                    scale=scale, block_kv=block_kv,
                                    kv_len=kv_len, prefix=prefix)


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
