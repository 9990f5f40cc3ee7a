"""moonlight-serve-8k's programs compile for one described TPU v5e chip at
the cell's own sizes (published widths, five layers, 64 rows, a 7936-
position latent cache) and fit its memory.

Nothing runs. The prefill of one chunk of rows into the resident cache
and the decode step are lowered for a ``v5e:2x2`` topology described
inside a fixture (never at import), with the persistent compile cache
off around the compiles. ``impl="auto"`` and the flash kernel's
qualification would take their CPU branches here, so the test steers
them to the Pallas kernels the chip runs.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import harness
from bench.drivers import serve_session

HBM = 15.75e9            # what XLA:TPU lets a program use of the 16 GiB


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


@pytest.fixture
def on_tpu(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_auto",
                        lambda impl: "pallas" if impl == "auto" else impl)
    monkeypatch.setattr(ops, "_require_tpu", lambda: None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def _used(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def test_moonlight_serve_programs_compile_and_fit(one_chip, on_tpu):
    from repro.core.registry import ActiveCodeRegistry
    from repro.launch import serve
    from repro.models import build_model
    from repro.serve.engine import (ServeEngine, default_sampler,
                                    make_serve_step)

    cell = harness.load_cell("moonlight-serve-8k")
    tr = cell.traffic
    B, P, MS, R = tr["batch"], tr["prompt_len"], tr["max_seq"], \
        tr["prefill_rows"]
    run = serve.build_run(cell.config["preset"], batch=B, max_seq=MS)
    run = run.replace(model=serve_session.program_model(cell.config))
    model = build_model(run.model)
    engine = ServeEngine(model, run, sampler_binding=ActiveCodeRegistry()
                         .bind("u", "sampler"))
    params = _sds(jax.eval_shape(model.init, jax.random.PRNGKey(0)), one_chip)
    cache = _sds(jax.eval_shape(lambda: model.init_cache(B, MS, engine.ctx)),
                 one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    decode = jax.jit(make_serve_step(model, engine.ctx, default_sampler),
                     donate_argnums=(2,)).lower(
        params, i32(B), cache, i32(),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)).compile()
    text = decode.as_text()
    assert "mla_decode" in text and "gmm" in text     # both kernels on the path
    assert _used(decode) < HBM
    # the engine's chunk prefill, as set-up calls it
    fill = engine.fill_program().lower(params, i32(R, P), cache,
                                       i32()).compile()
    assert "flash_attention" in fill.as_text()
    assert _used(fill) < HBM
