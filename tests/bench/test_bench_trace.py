"""The trace reduction: busy time, device ops and labelled idle gaps."""
import os

import pytest

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")


def _trace():
    # window 0..100 ns on the host; a loop at 10-40 holding two ops, and
    # one op at 70-90; names as the TPU trace gives them or already short
    ms = 1.0
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ["bench.window", 0.0, 100.0 * ms, {}],
        ["bench.train_step", 5.0, 40.0, {}],
        ["bench.deploy", 45.0, 20.0, {}],
        ["backend_compile", 50.0, 10.0, {}],
    ]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_train_step(3)", 10.0, 30.0, {}],
            ["jit_train_step(3)", 70.0, 20.0, {}],
            ["jit_train_step(3)", 95.0, 10.0, {}]]},
        {"name": "XLA Ops", "events": [
            ["%while.5 = (s32[]) while(...)", 10.0, 30.0, {}],
            ["%fusion.1 = bf16[8] fusion(...)", 10.0, 20.0, {}],
            ["rmsnorm_pallas.2", 30.0, 10.0, {}],
            ["fusion.1", 70.0, 20.0, {}]]}]}
    return {"planes": [host, dev]}


def test_busy_ops_modules_and_gaps():
    r = trace_reduce.reduce(_trace())
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(50e-9)          # 10-40 and 70-90
    # by self time: the loop's own time is what its body leaves, none
    assert r.top_ops(3) == [["jit_train_step/fusion.1", pytest.approx(40e-9)],
                            ["jit_train_step/rmsnorm_pallas.2",
                             pytest.approx(10e-9)],
                            ["jit_train_step/while.5", pytest.approx(0.0)]]
    # the run at 95 ns is cut by the window's end and is left out
    assert r.module_times("train_step") == [pytest.approx(30e-9),
                                            pytest.approx(20e-9)]
    # longest first; the two of 10 ns keep the order they come in
    assert [g[0] for g in r.gaps] == ["bench.deploy>backend_compile",
                                      "bench.train_step", "no host span"]
    assert [g[1] for g in r.gaps] == pytest.approx([30e-9, 10e-9, 10e-9])


def test_trace_without_a_device_is_refused():
    tr = _trace()
    tr["planes"] = tr["planes"][:1]
    with pytest.raises(ValueError, match="no TPU"):
        trace_reduce.reduce(tr)


def test_union():
    assert trace_reduce.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_recorded_tpu_trace():
    """A slice of a trace recorded on one TPU v5e in the smollm-train-steady
    cell: the end of one train step, the host's gap, the next step's start."""
    r = trace_reduce.reduce(trace_reduce.load(
        os.path.join(DATA, "train_step_gap.json.gz")))
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(0.140894401)
    assert r.busy_s == pytest.approx(0.119990679)
    assert r.top_ops(1) == [["jit_train_step/fusion.484",
                             pytest.approx(0.02107668)]]
    label, secs = r.gaps[0]
    assert label == "bench.train_step" and secs == pytest.approx(0.018362473)
    assert r.module_times("train_step") == []     # no whole step in the slice
