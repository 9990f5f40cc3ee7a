"""Kernel micro-benchmarks (XLA paths, CPU wall-time): blockwise
attention vs dense, SSD chunked vs naive scan."""
from __future__ import annotations

import time
from statistics import median

import jax
import jax.numpy as jnp

from repro.kernels import ref, xla


def timeit(fn, *args, n=10):
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return median(ts)


def main(report) -> None:
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, H, S, D = 1, 4, 1024, 64
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)

    rect = jax.jit(lambda q, k, v: xla.attention_blockwise(
        q, k, v, causal=True, block_kv=256))
    dense = jax.jit(lambda q, k, v: ref.attention_ref(q, k, v, causal=True))
    t_r = timeit(rect, q, k, v)
    t_d = timeit(dense, q, k, v)
    report("attn_dense_1k", t_d * 1e6, f"{t_d*1e3:.1f} ms")
    report("attn_blockwise_1k", t_r * 1e6, f"{t_r*1e3:.1f} ms")

    Bs, Ss, Hh, P, N = 2, 2048, 8, 64, 64
    x = jax.random.normal(ks[0], (Bs, Ss, Hh, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bs, Ss, Hh))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (Hh,)) * 0.5)
    Bm = jax.random.normal(ks[0], (Bs, Ss, N))
    Cm = jax.random.normal(ks[1], (Bs, Ss, N))
    Dk = jnp.ones((Hh,))
    chunked = jax.jit(lambda *a: xla.ssd_chunked(*a, chunk=128)[0])
    naive = jax.jit(lambda *a: ref.ssd_ref(*a)[0])
    t_c = timeit(chunked, x, dt, A, Bm, Cm, Dk)
    t_n = timeit(naive, x, dt, A, Bm, Cm, Dk)
    report("ssd_naive_2k", t_n * 1e6, f"{t_n*1e3:.1f} ms")
    report("ssd_chunked_2k", t_c * 1e6,
           f"{t_c*1e3:.1f} ms (x{t_n/t_c:.2f} vs naive scan)")


if __name__ == "__main__":
    main(lambda n, us, d: print(f"{n},{us:.1f},{d}"))
