"""Device time by named scope and by kernel, read from the raw trace.

The data is a chip trace: a 2-layer smollm-shaped model (remat on, the
rmsnorm Pallas kernel) taking two train steps and generating four
tokens on one TPU v5e, trimmed to the device plane's ``XLA Modules`` and
``XLA Ops`` lines (op metadata: name and ``tf_op``) and the host's
``bench.*`` spans. The expected totals were computed from the same file
by hand: every event at its picosecond offset, each op's own time as its
duration less that of the ops directly inside it, and its scope as the
last of the six scope names that its ``tf_op`` contains."""
import gzip
import os
from types import SimpleNamespace

import pytest

from bench import harness, scopes, trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tiny_train_serve.xplane.pb.gz")
# ns over the window's whole runs, by hand
TRAIN = {"(no scope)": 83451.796, "attention": 73794.688, "loss": 28942.5,
         "mlp": 21212.422, "optimizer": 9868.124, "unembed": 3251.172}
SERVE = {"(no scope)": 24857.886, "attention": 15509.844, "mlp": 1793.516,
         "unembed": 1636.172}


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    # laid out as a traced run leaves it: <OUT>/trace/<run>/<host>.xplane.pb
    out = tmp_path_factory.mktemp("out") / "trace" / "run" / "t.xplane.pb"
    out.parent.mkdir(parents=True)
    with gzip.open(DATA, "rb") as f:
        out.write_bytes(f.read())
    return str(out)


@pytest.mark.parametrize("module, runs, module_ns, rmsnorm_ns, want", [
    ("train_step", 2, 240210.0, 12192.422, TRAIN),
    ("serve_step", 3, 49553.75, 1534.844, SERVE),
])
def test_scopes_of_a_chip_trace(xplane, module, runs, module_ns, rmsnorm_ns,
                                want):
    st = scopes.step_ops(xplane, module)
    assert st.runs == runs
    assert st.module_s * 1e9 == pytest.approx(module_ns, abs=0.01)
    got = {k: v * 1e9 for k, v in st.by_scope().items() if v > 0}
    assert got == pytest.approx(want, abs=0.01)
    assert st.kernel_s("rmsnorm") * 1e9 == pytest.approx(rmsnorm_ns, abs=0.01)
    # own times add up to the time in which some op of the module runs
    runs = _runs(xplane, module)
    ops = [(s, s + d) for p in scopes.device_events(xplane)
           for n, s, d, _ in p["ops"] if any(r0 <= s <= r1 for r0, r1 in runs)]
    busy = sum(e - s for s, e in trace_reduce.union(ops))
    assert sum(secs for _, secs, _ in st.ops) * 1e9 == pytest.approx(busy)


def _runs(xplane, module):
    lo, hi = scopes.window(xplane)
    return [(s, s + d) for p in scopes.device_events(xplane)
            for n, s, d, _ in p["modules"]
            if module in n and s >= lo and s + d <= hi]


def test_attention_under_remat_and_transpose(xplane):
    st = scopes.step_ops(xplane, "train_step")
    mine = [(secs, tf) for _, secs, tf in st.ops
            if scopes.scope_of(tf) == "attention"]
    remat = sum(s for s, tf in mine if "rematted_computation" in tf)
    grad = sum(s for s, tf in mine if "transpose(" in tf)
    assert remat * 1e9 == pytest.approx(23325.468, abs=0.01)
    assert grad * 1e9 == pytest.approx(56275.546, abs=0.01)


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/bsd,dhk->bhsk/dot_general:",
     "attention"),
    ("jit(train_step)/transpose(jvp(unembed))/...d,vd->...v/dot_general:",
     "unembed"),
    ("jit(train_step)/jvp(loss)/jit(take_along_axis)/select_n:", "loss"),
    ("jit(serve_step)/while/body/closed_call/attention/jit(rmsnorm_pallas)/"
     "rmsnorm/pallas_call:", "attention"),
    ("jit(serve_step)/while/body/closed_call/jit(rmsnorm_pallas)/rmsnorm/"
     "pallas_call:", scopes.NO_SCOPE),
    ("jit(train_step)/optimizer/mul:", "optimizer"),
    ("jit(serve_step)/sampler/argmax:", "sampler"),
    ("jit(train_step)/mlp_apply/attentions/add:", scopes.NO_SCOPE),
    ("", scopes.NO_SCOPE),
])
def test_scope_of_a_name_stack(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def test_readers_read_the_run_trace_and_stay_silent_without(xplane,
                                                             monkeypatch):
    monkeypatch.setattr(harness, "OUT", type(harness.OUT)(xplane).parents[2])
    traced = SimpleNamespace(trace=object())
    got = harness.metric_reader("attention_ms.train").read(traced)
    assert got == pytest.approx(TRAIN["attention"] / 2 / 1e6)
    got = harness.metric_reader("attention_ms.serve").read(traced)
    assert got == pytest.approx(SERVE["attention"] / 3 / 1e6)
    assert harness.metric_reader("rmsnorm_ms").read(traced) == \
        pytest.approx(12192.422 / 2 / 1e6)
    # no such scope in the program (an older one): no number
    assert scopes.scope_ms(traced, r"train_step", "sampler") is None
    untraced = SimpleNamespace(trace=None)
    for name in ("attention_ms.train", "attention_ms.serve", "rmsnorm_ms"):
        assert harness.metric_reader(name).read(untraced) is None


def test_cli_prints_each_scope(xplane, capsys):
    assert scopes.main([xplane, "--module", "train_step"]) == 0
    out = capsys.readouterr().out
    assert "runs 2" in out
    for name in TRAIN:
        assert name in out
