"""Shape-aware sharding rules (single-process: uses an abstract mesh)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import make_run_config
from repro.sharding.auto import (
    logical_to_spec_shaped,
    run_rules,
    sanitize_spec,
)
from repro.sharding.specs import make_rules


@pytest.fixture()
def mesh():
    # abstract 16x16 mesh: no devices touched
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


def rules():
    return make_rules(("data", "model"))


def test_divisible_dims_shard(mesh):
    spec = logical_to_spec_shaped(("vocab", "embed"), (163840, 7168),
                                  rules(), mesh)
    assert spec == P("model", "data")


def test_indivisible_dim_skipped(mesh):
    # yi-34b: 56 heads on a 16-way axis -> replicated
    spec = logical_to_spec_shaped(("embed", "heads", "head_dim"),
                                  (7168, 56, 128), rules(), mesh)
    assert spec == P("data")


def test_indivisible_dim_does_not_shadow_later_dim(mesh):
    """The decode-cache bug: kv_heads=8 must NOT consume the model axis
    it cannot use — kv_seq gets it."""
    r = run_rules(make_run_config("qwen3-0.6b", "decode_32k"))
    spec = logical_to_spec_shaped(
        ("layers", "batch", "kv_heads", "kv_seq", "head_dim"),
        (28, 128, 8, 32768, 128), r, mesh)
    assert spec[3] == "model"          # kv_seq sharded
    assert spec[2] is None             # kv_heads replicated


def test_tuple_axis_prefix(mesh):
    # batch 32 divides 16 but not 16*16 when 'pod' absent; with the
    # 2-axis mesh ('pod','data') rule keeps only 'data'
    spec = logical_to_spec_shaped(("batch", "seq"), (32, 4096),
                                  rules(), mesh)
    assert spec[0] == "data"


def test_batch_one_replicated(mesh):
    spec = logical_to_spec_shaped(("batch", "seq"), (1, 524288),
                                  rules(), mesh)
    assert spec == P()                 # nothing shardable on dim 0


def test_sanitize_spec_drops_uneven(mesh):
    assert sanitize_spec((50280, 64), P("model", None), mesh) == P()
    assert sanitize_spec((50304, 64), P("model", None), mesh) == \
        P("model")


def test_run_rules_decode_kv_seq():
    r = run_rules(make_run_config("qwen3-0.6b", "decode_32k"))
    assert r.get("kv_seq") == "model"
    r2 = run_rules(make_run_config("qwen3-0.6b", "train_4k"))
    assert r2.get("kv_seq") is None


def test_sp_rules():
    run = make_run_config("yi-34b", "train_4k")   # SP on by default
    r = run_rules(run)
    assert r.get("seq") == "model"


def test_build_ctx_takes_the_default_kernels():
    """A run's context picks the kernels of ``ModelCtx()``; its decode
    context differs only in the forward-only Pallas paths of ssd and
    gmm (no mesh, so no sequence-sharded decode attention)."""
    from dataclasses import fields
    from repro.models.blocks import ModelCtx
    from repro.train.step import build_ctx

    run = make_run_config("smollm-135m", "train_4k")
    kernels = [f.name for f in fields(ModelCtx) if f.name.endswith("_impl")]
    assert len(kernels) == 6
    default, train = ModelCtx(), build_ctx(run)
    decode = build_ctx(run, decode=True)
    for name in kernels:
        assert getattr(train, name) == getattr(default, name), name
    assert {n for n in kernels if getattr(decode, n) != getattr(default, n)
            } == {"ssd_impl", "gmm_impl"}
    assert (decode.ssd_impl, decode.gmm_impl) == ("auto", "auto")


def test_auto_attention_is_blockwise_off_tpu():
    """The default ``attn_impl="auto"`` takes the blockwise XLA path on
    any backend but a TPU, at the widths of a training run."""
    from repro.kernels import ops, xla
    from repro.train.step import build_ctx

    ctx = build_ctx(make_run_config("smollm-135m", "train_4k"))
    assert ctx.attn_impl == "auto"
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 3, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 1, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 1, 256, 64), jnp.float32)
    assert not ops.flash_qualifies(q, k)
    got = ops.attention(q, k, v, impl=ctx.attn_impl)
    want = xla.attention_blockwise(q, k, v)
    assert jnp.array_equal(got, want)
