"""The main path's Pallas kernels compile for one TPU v5e chip.

Nothing runs: each kernel is lowered and compiled at real widths for a
described (not attached) ``v5e:2x2`` topology, which catches what the
interpreter cannot (block tiling, Mosaic lowering, VMEM limits). The
topology is described inside a fixture, never at import, and the
persistent compile cache is off around the compiles (an entry written
for a described chip cannot be read back without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
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


def test_rmsnorm_fwd_and_grad_compile(one_chip):
    x = jax.ShapeDtypeStruct((8192, 576), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((576,), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(rmsnorm_pallas, x, w)

    def loss(x, w):
        return jnp.sum(rmsnorm_pallas(x, w).astype(jnp.float32) ** 2)

    assert "tpu_custom_call" in _compiled_text(
        jax.grad(loss, argnums=(0, 1)), x, w)


def test_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((1, 9, 1024, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 3, 1024, 64), jnp.bfloat16,
                              sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(flash_attention_pallas,
                                               q, kv, kv)


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
    assert "tpu_custom_call" in text


def test_moe_gmm_compiles(one_chip):
    lhs = jax.ShapeDtypeStruct((8, 256, 1024), jnp.bfloat16,
                               sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((8, 1024, 512), jnp.bfloat16,
                               sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(moe_gmm_pallas, lhs, rhs)
