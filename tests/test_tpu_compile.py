"""The main path's Pallas kernels compile for one TPU v5e chip.

Nothing runs: each kernel is lowered and compiled at real widths for a
described (not attached) ``v5e:2x2`` topology, which catches what the
interpreter cannot (block tiling, Mosaic lowering, VMEM limits). The
topology is described inside a fixture, never at import, and the
persistent compile cache is off around the compiles (an entry written
for a described chip cannot be read back without one).
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mla_decode import mla_decode_pallas
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _kernel_named(text: str, name: str) -> bool:
    """The compiled program runs a Mosaic kernel whose instruction
    carries the ``pallas_call``'s ``name=``, as the trace shows it."""
    return re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call\(.*"
                     r"custom_call_target=\"tpu_custom_call\"", text) \
        is not None


def test_rmsnorm_fwd_and_grad_compile(one_chip):
    x = jax.ShapeDtypeStruct((8192, 576), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((576,), jnp.float32, sharding=one_chip)
    assert _kernel_named(_compiled_text(rmsnorm_pallas, x, w), "rmsnorm")

    def loss(x, w):
        return jnp.sum(rmsnorm_pallas(x, w).astype(jnp.float32) ** 2)

    assert _kernel_named(_compiled_text(
        jax.grad(loss, argnums=(0, 1)), x, w), "rmsnorm")


def test_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((1, 9, 1024, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 3, 1024, 64), jnp.bfloat16,
                              sharding=one_chip)
    assert _kernel_named(_compiled_text(flash_attention_pallas, q, kv, kv),
                         "flash_attention")


@pytest.mark.parametrize("B,Hq,Hkv,S,D", [
    (4, 9, 3, 4096, 64),        # smollm-135m training, 4 x 4096
    (32, 16, 8, 1024, 128),     # qwen3-0.6b prefill, batch 32 x 1024
])
def test_flash_attention_fwd_and_grad_compile_at_chip_widths(
        one_chip, B, Hq, Hkv, S, D):
    """The forward and the two backward kernels tile and fit VMEM at the
    widths the benchmark runs them."""
    q = jax.ShapeDtypeStruct((B, Hq, S, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, Hkv, S, D), jnp.bfloat16,
                              sharding=one_chip)
    assert _kernel_named(_compiled_text(flash_attention_pallas, q, kv, kv),
                         "flash_attention")

    def loss(q, k, v):
        return jnp.sum(flash_attention_pallas(q, k, v).astype(jnp.float32))

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    for name in ("flash_attention", "flash_attention_dkv",
                 "flash_attention_dq"):
        assert _kernel_named(text, name), name


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    # mamba2-370m: d_inner 2048 = 32 heads x 64, state 128, chunk 128
    B, S, H, P, N = 1, 2048, 32, 64, 128

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compiled_text(
        lambda *a: ssd_scan_pallas(*a, chunk=128),
        sds((B, S, H, P), jnp.bfloat16), sds((B, S, H)), sds((H,)),
        sds((B, S, N), jnp.bfloat16), sds((B, S, N), jnp.bfloat16),
        sds((H,)))
    assert _kernel_named(text, "ssd_scan")


def test_moe_gmm_compiles(one_chip):
    lhs = jax.ShapeDtypeStruct((8, 256, 1024), jnp.bfloat16,
                               sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((8, 1024, 512), jnp.bfloat16,
                               sharding=one_chip)
    assert _kernel_named(_compiled_text(moe_gmm_pallas, lhs, rhs), "moe_gmm")


def test_mla_decode_reads_a_stacked_cache_in_place(one_chip):
    """moonlight-serve-8k's latent decode: the kernel takes every layer's
    [4, 64, 576, 7936] cache whole, with the layer as a prefetched
    scalar, and the program slices and copies no layer out of it."""
    L, B, H, C, T = 4, 64, 16, 576, 7936

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compiled_text(
        lambda q, c, l, row, pos: mla_decode_pallas(q, c, l, row, pos,
                                                    r=512, scale=0.07),
        sds((B, H, C)), sds((L, B, C, T)), sds((), jnp.int32),
        sds((B, C, 1)), sds((), jnp.int32))
    assert _kernel_named(text, "mla_decode")
    assert f"[{B},{C},{T}]" not in text
