"""moonlight-serve-8k and smollm-train-abflip on the CPU at tiny sizes:
the program's latent-attention MoE decoder against the plain reference
(``bench/reference/mla_moe_lm.py``) on seeded random weights, the
configuration check, the work counts, the per-layer readers on a small
trace, and both cells' drivers end to end."""
import copy
import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, mla_moe_counts, mla_moe_weights
from bench.drivers import serve_session
from bench.reference import mla_moe_lm
import tiny

# the configuration at a CPU size: every width cut, listed in ``reduced``
TINY = {"num_hidden_layers": 3, "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "moe_intermediate_size": 32, "n_routed_experts": 8,
        "num_experts_per_tok": 2, "vocab_size": 512}
CELL = "moonlight-serve-8k"
SEED = 2**31 + 12345


def tiny_config(dtype="float32"):
    cfg = copy.deepcopy(harness.load_json(
        harness.BENCH / "configs" / "moonlight-16b-a3b.json"))
    cfg.update(TINY)
    cfg["reduced"] = sorted(set(cfg["reduced"]) | set(TINY))
    cfg["dtypes"].update(params=dtype, compute=dtype)
    return cfg


def f32_preset(monkeypatch):
    """The preset in float32, so that the CPU run can match the f32
    reference to rounding."""
    from repro.configs import ARCH_REGISTRY
    name = "moonlight-16b-a3b"
    monkeypatch.setitem(ARCH_REGISTRY, name, dataclasses.replace(
        ARCH_REGISTRY[name], param_dtype="float32", dtype="float32"))


@pytest.fixture
def program(monkeypatch):
    from repro.models import build_model
    f32_preset(monkeypatch)
    cfg = tiny_config()
    mc = serve_session.program_model(cfg)
    m = serve_session.sizes(cfg)
    params = mla_moe_weights.make_params(7, m, 1.0, "float32")
    return build_model(mc), params, m


def _ctx():
    from repro.models.blocks import ModelCtx
    return ModelCtx(attn_impl="blockwise", moe_impl="dropless",
                    gmm_impl="xla", remat_policy="none")


def test_prefill_logits_match_the_reference(program):
    model, params, m = program
    toks = np.random.default_rng(0).integers(0, m["vocab"], (2, 40)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        full, _ = model.forward(params, jnp.asarray(toks), _ctx())
        cache = model.init_cache(2, 64, _ctx())
        last, _, _ = model.prefill(params, jnp.asarray(toks), cache, _ctx())
        for r in range(2):
            ref = mla_moe_lm.logits(params, toks[r], m)
            np.testing.assert_allclose(np.asarray(full[r]), np.asarray(ref),
                                       atol=2e-5, rtol=2e-5)
            np.testing.assert_allclose(np.asarray(last[r]),
                                       np.asarray(ref[-1]), atol=2e-5,
                                       rtol=2e-5)


def test_absorbed_decode_through_the_latent_cache_matches_the_reference(
        program):
    """Prefill 20 ids, then feed 12 more one at a time through the
    absorbed decode step: every step's logits equal the reference's
    full forward at that position."""
    model, params, m = program
    toks = np.random.default_rng(1).integers(0, m["vocab"], (2, 32)).astype(
        np.int32)
    P = 20
    with jax.default_matmul_precision("highest"):
        cache = model.init_cache(2, 48, _ctx())
        _, cache, pos = model.prefill(params, jnp.asarray(toks[:, :P]), cache,
                                      _ctx())
        got = []
        for t in range(P, 32):
            lg, cache = model.decode_step(params, jnp.asarray(toks[:, t]),
                                          cache, pos, _ctx())
            got.append(lg)
            pos = pos + 1
        got = jnp.stack(got, 1)
        for r in range(2):
            ref = mla_moe_lm.logits(params, toks[r], m, rows=range(P, 32))
            np.testing.assert_allclose(np.asarray(got[r]), np.asarray(ref),
                                       atol=2e-5, rtol=2e-5)


def test_reference_rope_is_the_published_layout():
    """Rotating de-interleaved pairs: the pair (x0, x1) at position p
    turns by p * theta^0, as the modelling code's view/transpose does."""
    x = jnp.zeros((3, 1, 8)).at[:, 0, 0].set(1.0)
    out = mla_moe_lm.rope(x, jnp.arange(3), 10000.0)
    # x0 goes to slot 0, its partner x1 to slot 4 (half 8 / 2)
    np.testing.assert_allclose(np.asarray(out[:, 0, 0]), np.cos(np.arange(3)),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(out[:, 0, 4]), np.sin(np.arange(3)),
                               atol=1e-6)


def test_configuration_check(monkeypatch):
    cfg = harness.load_json(harness.BENCH / "configs" / "moonlight-16b-a3b.json")
    mc = serve_session.program_model(cfg)
    assert mc.num_layers == 5 and mc.first_k_dense == 1
    assert cfg["num_hidden_layers"] == 5
    assert cfg["published"]["num_hidden_layers"] == 27
    bad = copy.deepcopy(cfg)
    bad["num_experts_per_tok"] = bad["published"]["num_experts_per_tok"] = 8
    with pytest.raises(harness.BenchError, match="num_experts_per_tok"):
        serve_session.program_model(bad)
    bad = copy.deepcopy(cfg)
    bad["published"]["scoring_func"] = "softmax"
    with pytest.raises(harness.BenchError, match="scoring_func"):
        serve_session.program_model(bad)
    # a program without the preset (an older checkout) fails at once
    from repro import configs
    monkeypatch.delitem(configs.ARCH_REGISTRY, "moonlight-16b-a3b")
    with pytest.raises(harness.BenchError, match="no preset"):
        serve_session.program_model(cfg)


def test_counts_by_hand():
    m = {"layers": 3, "dense_layers": 1, "d_model": 8, "heads": 2,
         "q_lora_rank": 0, "kv_lora_rank": 4, "qk_nope_head_dim": 2,
         "qk_rope_head_dim": 2, "v_head_dim": 3, "d_ff": 16, "experts": 4,
         "experts_per_token": 2, "moe_d_ff": 5, "shared_experts": 2,
         "vocab": 10}
    # q 8*2*4, latent 8*6, W_UK 4*2*2, W_UV 4*2*3, o 2*3*8
    attn = 64 + 48 + 16 + 24 + 48
    assert mla_moe_counts.attn_params(m) == attn == 200
    expert, shared = 3 * 8 * 5, 3 * 8 * 10
    active = 3 * attn + 3 * 8 * 16 + 2 * (2 * expert + shared + 8 * 4) + 80
    assert mla_moe_counts.active_params(m) == active == 2088
    live = 7.0
    attn_f = 2 * 2 * live * (6 + 4)
    assert mla_moe_counts.decode_step_flops(m, 3, live) == \
        3 * (2 * active + 3 * attn_f)
    assert mla_moe_counts.mla_decode_bytes(m, 3, live) == \
        3 * 3 * 8 * 6 * 2 + 3 * (attn + 4) * 2
    assert mla_moe_counts.moe_decode_bytes(m, 5) == \
        5 * expert * 2 + 2 * (shared * 2 + 9 * 4 * 4)
    q_lora = dict(m, q_lora_rank=3)
    assert mla_moe_counts.attn_params(q_lora) == attn - 64 + 8 * 3 + 3 * 2 * 4


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _msg(*fields):
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint((num << 3) | 2) + _varint(len(v)) + v
    return out


def _plane(pid, name, lines, metas, stat_names=()):
    parts = [(1, pid), (2, name)] + [(3, ln) for ln in lines]
    for mid, mname, stats in metas:
        md = _msg((1, mid), (2, mname),
                  *[(5, _msg((1, sid), (5, val))) for sid, val in stats])
        parts.append((4, _msg((1, mid), (2, md))))
    for sid, sname in stat_names:
        parts.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    return _msg(*parts)


def _line(lid, name, events):
    return _msg((1, lid), (2, name), (3, 0),
                *[(4, _msg((1, mid), (2, off), (3, dur)))
                  for mid, off, dur in events])


S = "jit(serve_step)/while/body/closed_call/"
# (metadata id, op, name stack, start and duration in ns within a run)
OPS = [(10, "mla_decode.1", S + "attention/mla_decode/mla_decode:", 0, 3000),
       (11, "fusion.2", S + "mlp/moe_router/top_k:", 3000, 1000),
       (12, "gmm.3", S + "mlp/moe_experts/gmm:", 4000, 3000),
       (13, "fusion.4", S + "mlp/moe_shared/mlp/dot_general:", 7000, 1000),
       (14, "copy.5", S + "dynamic_slice:", 8000, 2000)]


@pytest.fixture
def trace_run(tmp_path, monkeypatch):
    """A hand-made trace of two 10 us decode steps in a 40 us window,
    laid out as a traced run leaves it, and a run that reads it."""
    host = _plane(1, "/host:CPU", [_line(1, "main", [(1, 0, 40_000_000)])],
                  [(1, "bench.window", [])])
    mods, ops = [], []
    for run0 in (1000, 21000):
        mods.append((2, run0 * 1000, 10_000_000))
        ops += [(mid, (run0 + off) * 1000, dur * 1000)
                for mid, _, _, off, dur in OPS]
    metas = [(2, "jit_serve_step(7)", [])] + \
        [(mid, op, [(99, tf)]) for mid, op, tf, _, _ in OPS]
    dev = _plane(2, "/device:TPU:0", [_line(2, "XLA Modules", mods),
                                      _line(3, "XLA Ops", ops)],
                 metas, [(99, "tf_op")])
    path = tmp_path / "trace" / "run" / "t.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_msg((1, host), (1, dev)))
    monkeypatch.setattr(harness, "OUT", tmp_path)
    m = serve_session.sizes(harness.load_json(
        harness.BENCH / "configs" / "moonlight-16b-a3b.json"))
    loads = [np.array([[6, 0, 2, 4], [3, 3, 3, 3]]),
             np.array([[12, 0, 0, 0], [0, 4, 4, 4]])]
    return SimpleNamespace(
        trace=object(), m=m, B=2, expert_loads=loads,
        traced_positions=lambda: [99, 100, 101],
        cell=SimpleNamespace(peak=harness.peak_for("TPU v5 lite")))


def test_readers_on_a_small_trace(trace_run):
    read = lambda name: harness.metric_reader(name).read(trace_run)  # noqa
    assert read("mla_decode_ms.serve") == pytest.approx(3000 / 1e6)
    assert read("moe_decode_ms.serve") == pytest.approx(5000 / 1e6)
    m, peak = trace_run.m, trace_run.cell.peak
    live = 101.0                                  # mean of 100, 101, 102
    nbytes = mla_moe_counts.mla_decode_bytes(m, 2, live)
    assert read("mla_decode_hbm_share") == pytest.approx(
        100 * nbytes / 3e-6 / peak["hbm_bytes_per_s"])
    distinct = (3 + 4 + 1 + 3) / 2                # experts hit, mean of steps
    nbytes = mla_moe_counts.moe_decode_bytes(m, distinct)
    assert read("expert_hbm_share.serve") == pytest.approx(
        100 * nbytes / 5e-6 / peak["hbm_bytes_per_s"])
    flops = mla_moe_counts.decode_step_flops(m, 2, live)
    assert read("decode_mfu.moe") == pytest.approx(
        100 * flops / 10e-6 / peak["bf16_flops_per_s"])
    assert read("moe_load_imbalance.serve") == pytest.approx(
        (6 / 3 + 1 + 4 + 4 / 3) / 4)


@pytest.mark.parametrize("name", [
    "mla_decode_ms.serve", "moe_decode_ms.serve", "mla_decode_hbm_share",
    "expert_hbm_share.serve", "decode_mfu.moe", "moe_load_imbalance.serve"])
def test_readers_stay_silent_without_what_they_read(name, trace_run):
    untraced = SimpleNamespace(trace=None, expert_loads=[],
                               traced_positions=lambda: [])
    assert harness.metric_reader(name).read(untraced) is None
    # another driver's run (no positions, no counter)
    other = SimpleNamespace(trace=None, calls=[])
    assert harness.metric_reader(name).read(other) is None


def _moon_cell(dtype="float32"):
    tr = copy.deepcopy(harness.load_json(
        harness.BENCH / "traffic" / "serve_session_8k.json"))
    tr.update(batch=4, prompt_len=32, new_tokens=8, max_seq=128,
              prefill_rows=2, check_rows=2)
    return harness.load_cell(CELL, config=tiny_config(dtype), traffic=tr)


def test_moonlight_cell_runs_and_is_correct(monkeypatch):
    f32_preset(monkeypatch)
    out = harness.run_cell(CELL, SEED, 2.0, False, allow_cpu=True,
                           cell=_moon_cell(), log=lambda *a, **k: None)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["decode_tokens_per_s"]["value"] > 0
    assert set(out["metrics"]) == {"decode_tokens_per_s", "setup_s"}


def test_moonlight_control_is_not_correct(monkeypatch):
    """The reference in float8 in the program's place reads a mean gap
    over the cell's limit; the program's own tokens read none."""
    f32_preset(monkeypatch)
    cell = _moon_cell()
    drv = serve_session.Driver(cell, SEED, harness.CompileLog())
    with harness.CompileLog() as clog:
        drv.setup()
        drv.window(1.5, harness.Tracer(False))
    assert clog.between(drv.t0, drv.t1) == []         # nothing compiled
    drv.release()
    picks = drv.sample()
    assert drv.widest_gap(picks) == pytest.approx(0.0, abs=1e-4)
    assert drv.gaps(picks, "fp8").mean() > cell.limits["logit_gap_mean"]


def test_abflip_cell_flips_without_compiling():
    cfg = tiny.tiny_config("smollm-135m")
    tr = copy.deepcopy(harness.load_json(
        harness.BENCH / "traffic" / "train_abflip.json"))
    tr.update(seq=32, batch=4)
    tr["deploys"].update(first_s=0.5, every_s=1.0)
    cell = harness.load_cell("smollm-train-abflip", config=cfg, traffic=tr)
    drv = harness.driver_module("train_abflip").Driver(
        cell, SEED, harness.CompileLog())
    with harness.CompileLog() as clog:
        drv.setup()
        drv.window(3.2, harness.Tracer(False))
        compiles = clog.between(drv.t0, drv.t1)
    md5s = [d["md5"] for d in drv.deploys]
    assert len(md5s) >= 3 and md5s[0] == md5s[2] != md5s[1]
    assert all(d["t_effect"] is not None for d in drv.deploys)
    assert compiles == []
    readings = drv.check()
    assert drv.failed == 0
    assert all(v <= lim for v, lim in readings.values() if lim is not None)
    assert "swap_to_effect_s" in drv.end_to_end()
