"""Per-kernel correctness sweeps: Pallas (called with interpret=True, so
the Pallas interpreter runs them on the CPU) and the XLA fast paths
against the pure-jnp oracles in ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, xla
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 128), (2, 7, 256), (1, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_pallas_vs_ref(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, shape, dtype)
    w = jax.random.normal(k2, shape[-1:], dtype)
    got = rmsnorm_pallas(x, w, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("shape", [(4, 128), (2, 7, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_pallas_grad_vs_ref(shape, dtype):
    """The custom VJP agrees with autodiff through the jnp oracle."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(9), 3)
    x = jax.random.normal(k1, shape, dtype)
    w = jax.random.normal(k2, shape[-1:], dtype)
    g = jax.random.normal(k3, shape, jnp.float32)

    def loss(norm):
        return lambda x, w: jnp.sum(norm(x, w).astype(jnp.float32) * g)

    got = jax.grad(loss(lambda x, w: rmsnorm_pallas(x, w, interpret=True)),
                   argnums=(0, 1))(x, w)
    want = jax.grad(loss(ref.rmsnorm_ref), argnums=(0, 1))(x, w)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol(dtype))


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # (B, Hq, Hkv, Sq, Skv, D, causal, window)
    (1, 4, 4, 128, 128, 64, True, 0),
    (2, 4, 2, 128, 128, 64, True, 0),       # GQA
    (1, 2, 1, 256, 256, 32, True, 64),      # sliding window
    (1, 2, 2, 128, 128, 64, False, 0),      # bidirectional (encoder)
    (1, 4, 4, 64, 192, 64, True, 0),        # decode offset (Sq < Skv)
    (1, 1, 1, 96, 96, 48, True, 0),         # odd sizes (block clamping)
]


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D,causal,window", ATTN_CASES)
def test_flash_attention_pallas_vs_ref(B, Hq, Hkv, Sq, Skv, D, causal,
                                       window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, Skv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, Skv, D), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# (B, Hq, Hkv, Sq, Skv, D, causal, window, dtype); sequences of 384 and
# 640 are 3 and 5 tiles of 128, so causal cases hold fully masked tiles
FLASH_VJP_CASES = [
    (1, 2, 2, 384, 384, 64, True, 0, jnp.float32),     # group 1
    (1, 4, 2, 384, 384, 128, True, 0, jnp.bfloat16),   # group 2
    (1, 3, 1, 384, 384, 64, False, 0, jnp.float32),    # group 3
    (1, 3, 1, 384, 384, 128, True, 0, jnp.float32),
    (1, 2, 1, 384, 384, 64, False, 0, jnp.bfloat16),
    (1, 3, 1, 384, 384, 64, True, 0, jnp.bfloat16),
    (1, 2, 2, 640, 640, 64, True, 192, jnp.float32),   # band skips tiles
    (1, 2, 1, 128, 384, 64, True, 0, jnp.float32),     # decode offset
    (2, 2, 2, 256, 256, 64, True, 0, jnp.float32),     # one masked tile
]


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D,causal,window,dtype",
                         FLASH_VJP_CASES)
def test_flash_attention_pallas_vjp_vs_ref(B, Hq, Hkv, Sq, Skv, D, causal,
                                           window, dtype):
    """Forward and (dq, dk, dv) of the custom VJP against autodiff through
    the f32 oracle."""
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Skv, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Skv, D), dtype)
    g = jax.random.normal(ks[3], (B, Hq, Sq, D), jnp.float32)

    def kernel(q, k, v):
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      interpret=True)

    def oracle(q, k, v):
        f32 = jnp.float32
        return ref.attention_ref(q.astype(f32), k.astype(f32), v.astype(f32),
                                 causal=causal, window=window)

    o, vjp = jax.vjp(kernel, q, k, v)
    o_ref, vjp_ref = jax.vjp(oracle, q, k, v)
    grads = vjp(g.astype(o.dtype))
    grads_ref = vjp_ref(g)
    bound = 1e-2 if dtype == jnp.bfloat16 else 1e-5
    assert o.dtype == dtype
    assert _rel(o, o_ref) < bound
    for name, a, b in zip("qkv", grads, grads_ref):
        assert a.dtype == dtype, name
        assert _rel(a, b) < bound, name


def test_flash_blocks_fit_vmem_at_chip_widths():
    """Tiles of up to 1024 rows where the sequence allows, under the VMEM
    budget, at the training and prefill widths the benchmark runs."""
    from repro.kernels import flash_attention as fa
    assert fa.pick_blocks(4096, 4096, 64) == (1024, 1024)
    assert fa.pick_blocks(1024, 1024, 128) == (1024, 1024)
    assert fa.pick_blocks(384, 384, 64) == (128, 128)
    for sq, d, itemsize in [(4096, 64, 2), (1024, 128, 2), (8192, 256, 4)]:
        bq, bk = fa.pick_blocks(sq, sq, d, itemsize)
        assert sq % bq == 0 and sq % bk == 0
        assert fa._vmem_bytes(bq, bk, d, itemsize) <= fa.VMEM_BUDGET


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D,causal,window", ATTN_CASES)
@pytest.mark.parametrize("entry", ["xla", "ops_auto"])
def test_blockwise_xla_vs_ref(B, Hq, Hkv, Sq, Skv, D, causal, window,
                              entry):
    """The blockwise path, called directly and through
    ``ops.attention(impl="auto")``, which takes it off a TPU."""
    attend = {"xla": xla.attention_blockwise,
              "ops_auto": lambda *a, **kw: ops.attention(*a, impl="auto",
                                                         **kw)}[entry]
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, Skv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, Skv, D), jnp.float32)
    got = attend(q, k, v, causal=causal, window=window, block_kv=64)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 4, 128, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 2, 128, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 2, 128, 64), jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_attention_kv_len_mask():
    """Dynamic KV prefix mask (decode path, dense/blockwise only)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (2, 2, 1, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 2, 64, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 2, 64, 32), jnp.float32)
    kv_len = jnp.array([3, 64], jnp.int32)
    got = xla.attention_blockwise(q, k, v, causal=False, kv_len=kv_len,
                                  block_kv=16)
    want = ref.attention_ref(q, k, v, causal=False, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_traced_window():
    """window may be a traced scalar (hymba's per-layer schedule scans)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 64, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 64, 32), jnp.float32)

    @jax.jit
    def f(w):
        return xla.attention_blockwise(q, k, v, causal=True, window=w,
                                       block_kv=16)

    np.testing.assert_allclose(np.asarray(f(jnp.int32(16))),
                               np.asarray(ref.attention_ref(
                                   q, k, v, causal=True, window=16)),
                               atol=2e-5, rtol=2e-5)
    # w == 0 means full attention, also when traced
    np.testing.assert_allclose(np.asarray(f(jnp.int32(0))),
                               np.asarray(ref.attention_ref(
                                   q, k, v, causal=True, window=0)),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# SSD (mamba2)
# ---------------------------------------------------------------------------

def _ssd_inputs(B=2, S=64, H=4, P=16, N=8, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N), dtype)
    Cm = jax.random.normal(ks[4], (B, S, N), dtype)
    D = jnp.ones((H,))
    return x, dt, A, Bm, Cm, D


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_ssd_chunked_vs_ref(chunk):
    x, dt, A, Bm, Cm, D = _ssd_inputs()
    y_ref, s_ref = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    y, s = xla.ssd_chunked(x, dt, A, Bm, Cm, D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("S,chunk", [(64, 16), (128, 32)])
def test_ssd_pallas_vs_ref(S, chunk):
    x, dt, A, Bm, Cm, D = _ssd_inputs(S=S)
    y_ref, s_ref = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    y, s = ssd_scan_pallas(x, dt, A, Bm, Cm, D, chunk=chunk,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=1e-4, rtol=1e-4)


def test_ssd_decode_matches_prefill():
    """Running the recurrence one token at a time from the chunked
    prefill state must match the full-sequence result."""
    x, dt, A, Bm, Cm, D = _ssd_inputs(S=32)
    y_full, s_full = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    y_pre, state = xla.ssd_chunked(x[:, :24], dt[:, :24], A, Bm[:, :24],
                                   Cm[:, :24], D, chunk=8)
    ys = []
    for t in range(24, 32):
        y_t, state = ref.ssd_decode_ref(x[:, t], dt[:, t], A, Bm[:, t],
                                        Cm[:, t], state, D)
        ys.append(y_t)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_full[:, 24:]),
                               atol=1e-4, rtol=1e-4)


def test_ssd_init_state_continuation():
    x, dt, A, Bm, Cm, D = _ssd_inputs(S=64)
    y_full, s_full = xla.ssd_chunked(x, dt, A, Bm, Cm, D, chunk=16)
    y1, s1 = xla.ssd_chunked(x[:, :32], dt[:, :32], A, Bm[:, :32],
                             Cm[:, :32], D, chunk=16)
    y2, s2 = xla.ssd_chunked(x[:, 32:], dt[:, 32:], A, Bm[:, 32:],
                             Cm[:, 32:], D, init_state=s1, chunk=16)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, 32:]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Grouped matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E,C,K,N", [(4, 32, 64, 48), (1, 8, 16, 16),
                                     (6, 100, 96, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_pallas_vs_ref(E, C, K, N, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    lhs = jax.random.normal(k1, (E, C, K), dtype)
    rhs = jax.random.normal(k2, (E, K, N), dtype)
    got = moe_gmm_pallas(lhs, rhs, interpret=True)
    want = ref.gmm_ref(lhs, rhs)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_gmm_xla_vs_ref():
    k1, k2 = jax.random.split(jax.random.PRNGKey(8))
    lhs = jax.random.normal(k1, (3, 16, 32), jnp.float32)
    rhs = jax.random.normal(k2, (3, 32, 24), jnp.float32)
    np.testing.assert_allclose(np.asarray(xla.gmm(lhs, rhs)),
                               np.asarray(ref.gmm_ref(lhs, rhs)),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# Dispatch layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("call", [
    lambda: ops.rmsnorm(jnp.ones((4, 32)), jnp.ones((32,)), impl="pallas"),
    lambda: ops.attention(*[jnp.ones((1, 1, 8, 16))] * 3, impl="pallas"),
    lambda: ops.ssd(*_ssd_inputs(S=16)[:5], impl="pallas"),
    lambda: ops.gmm(jnp.ones((1, 8, 16)), jnp.ones((1, 16, 8)),
                    impl="pallas"),
])
def test_ops_pallas_refuses_non_tpu_backend(call):
    """impl="pallas" never drops into the interpreter behind the
    caller's back; only a direct ``interpret=True`` call does."""
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        call()


def test_ops_dispatch_auto_is_xla_on_cpu():
    x = jnp.ones((4, 32))
    w = jnp.ones((32,))
    np.testing.assert_allclose(np.asarray(ops.rmsnorm(x, w, impl="auto")),
                               np.asarray(ref.rmsnorm_ref(x, w)),
                               atol=1e-6)
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)
    assert jnp.array_equal(ops.attention(q, k, v, impl="auto"),
                           xla.attention_blockwise(q, k, v))


def _qualifying():
    q = jnp.zeros((1, 4, 256, 64), jnp.bfloat16)
    k = jnp.zeros((1, 2, 256, 64), jnp.bfloat16)
    return q, k


@pytest.mark.parametrize("change,takes_flash", [
    ({}, True),
    ({"mesh": jax.sharding.AbstractMesh((1,), ("model",))}, True),
    ({"window": 128}, True),
    ({"window": jnp.asarray(128)}, False),          # traced window
    ({"prefix": 4}, False),
    ({"kv_len": jnp.array([200], jnp.int32)}, False),
    ({"q_start": 0}, False),
    ({"mesh": jax.sharding.AbstractMesh((2,), ("model",))}, False),
    ({"q": jnp.zeros((1, 4, 200, 64), jnp.bfloat16)}, False),
    ({"q": jnp.zeros((1, 4, 256, 48), jnp.bfloat16),
      "k": jnp.zeros((1, 2, 256, 48), jnp.bfloat16)}, False),
])
def test_flash_qualifies_rule(monkeypatch, change, takes_flash):
    """Which calls ``impl="auto"`` hands the kernel, with the backend
    steered to a TPU; every other call keeps the blockwise path."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q, k = _qualifying()
    kw = dict(change)
    q, k = kw.pop("q", q), kw.pop("k", k)
    assert ops.flash_qualifies(q, k, **kw) is takes_flash


@pytest.mark.parametrize("kw", [{}, {"prefix": 4}, {"q_start": 0},
                                {"mesh": jax.sharding.AbstractMesh(
                                    (2,), ("model",))}])
def test_ops_attention_auto_dispatch(monkeypatch, kw):
    """``ops.attention(impl="auto")`` calls the kernel exactly where the
    rule qualifies the call (backend steered to a TPU, kernels spied)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    calls = []
    monkeypatch.setattr(ops, "flash_attention_pallas",
                        lambda *a, **k: calls.append("flash") or a[0])
    monkeypatch.setattr(ops._xla, "attention_blockwise",
                        lambda *a, **k: calls.append("blockwise") or a[0])
    q, k = _qualifying()
    ops.attention(q, k, k, impl="auto", **kw)
    assert calls == ["flash" if not kw else "blockwise"]


@pytest.mark.parametrize("impl", ["dense", "blockwise_tri", "xla"])
def test_ops_attention_refuses_other_names(impl):
    """Attention takes auto, pallas, ref and blockwise only; any other
    name is refused, not run as the blockwise path."""
    q, k = _qualifying()
    with pytest.raises(ValueError, match="unknown attention impl"):
        ops.attention(q, k, k, impl=impl)
