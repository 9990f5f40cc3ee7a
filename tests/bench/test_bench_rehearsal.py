"""Each cell's programs compile for one described TPU v5e chip at the
cell's own sizes, and fit its memory.

Nothing runs. The train step, the prefill and the decode step are
lowered for a ``v5e:2x2`` topology described inside a fixture (never at
import), with the persistent compile cache off around the compiles.
``impl="auto"`` would take its CPU branch here, so the tests steer it
to the Pallas kernels the chip runs.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import harness

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
def pallas(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_auto",
                        lambda impl: "pallas" if impl == "auto" else impl)
    monkeypatch.setattr(ops, "_require_tpu", lambda: None)


def _sds(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM, used
    return used


def test_train_step_compiles_and_fits(one_chip, pallas):
    from repro.core.registry import ActiveCodeRegistry
    from repro.launch import train
    from repro.models import build_model
    from repro.optim.api import build_optimizer
    from repro.train import HotSwapTrainStep, init_state

    cell = harness.load_cell("smollm-train-steady")
    B, S = cell.traffic["batch"], cell.traffic["seq"]
    run = train.build_run(cell.config["preset"], seq=S, batch=B)
    run = run.replace(model=harness.program_model(cell.config))
    model = build_model(run.model)
    opt = build_optimizer(run.train, run.model.param_dtype)
    state = jax.eval_shape(lambda k: init_state(model, opt, k, run),
                           jax.random.PRNGKey(0))
    reg = ActiveCodeRegistry()
    step = HotSwapTrainStep(model, run, opt, {
        s: reg.bind("analyst", s) for s in HotSwapTrainStep.SLOTS})
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    compiled = step._build(step._resolve()[1]).lower(
        _sds(state, one_chip), batch).compile()
    _fits(compiled)
    assert "rmsnorm_pallas" in compiled.as_text()     # the kernel is on the path


def test_serve_programs_compile_and_fit(one_chip, pallas):
    from repro.launch import serve
    from repro.models import build_model
    from repro.serve.engine import default_sampler, make_serve_step
    from repro.train.step import build_ctx

    cell = harness.load_cell("qwen3-serve-steady")
    tr = cell.traffic
    B, P, MS = tr["batch"], tr["prompt_len"], tr["max_seq"]
    run = serve.build_run(cell.config["preset"], batch=B, max_seq=MS)
    run = run.replace(model=harness.program_model(cell.config))
    model = build_model(run.model)
    ctx = build_ctx(run, decode=True)
    params = _sds(jax.eval_shape(model.init, jax.random.PRNGKey(0)), one_chip)
    cache = _sds(jax.eval_shape(lambda: model.init_cache(B, MS, ctx)),
                 one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    prefill = jax.jit(lambda p, t, c: model.prefill(p, t, c, ctx)).lower(
        params, i32(B, P), cache).compile()
    decode = jax.jit(make_serve_step(model, ctx, default_sampler),
                     donate_argnums=(2,)).lower(
        params, i32(B), cache, i32(),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)).compile()
    _fits(prefill)
    _fits(decode)
    assert "rmsnorm_pallas" in prefill.as_text()
    assert "rmsnorm_pallas" in decode.as_text()
