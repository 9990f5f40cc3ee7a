"""``cache_write_ms.serve`` on a hand-made trace: two 10 us decode steps
in a 40 us window, each with the layer scan's ops and the write of its
new cache rows after it, laid out as a traced run leaves it."""
from types import SimpleNamespace

import pytest
from test_bench_mla_moe import _line, _msg, _plane

from bench import harness

S = "jit(serve_step)/"
# (metadata id, op, name stack, start and duration in ns within a run)
SCAN = [(10, "fusion.1", S + "while/body/closed_call/attention/select_n:",
         0, 6000),
        (11, "fusion.2", S + "while/body/closed_call/mlp/dot_general:",
         6000, 3000)]
WRITE = [(12, "dynamic-update-slice.3",
          S + "cache_write/dynamic_update_slice:", 9000, 400),
         (13, "dynamic-update-slice.4",
          S + "cache_write/dynamic_update_slice:", 9400, 200)]


def _run(tmp_path, monkeypatch, ops):
    host = _plane(1, "/host:CPU", [_line(1, "main", [(1, 0, 40_000_000)])],
                  [(1, "bench.window", [])])
    mods, evs = [], []
    for run0 in (1000, 21000):
        mods.append((2, run0 * 1000, 10_000_000))
        evs += [(mid, (run0 + off) * 1000, dur * 1000)
                for mid, _, _, off, dur in ops]
    metas = [(2, "jit_serve_step(7)", [])] + \
        [(mid, op, [(99, tf)]) for mid, op, tf, _, _ in ops]
    dev = _plane(2, "/device:TPU:0", [_line(2, "XLA Modules", mods),
                                      _line(3, "XLA Ops", evs)],
                 metas, [(99, "tf_op")])
    path = tmp_path / "trace" / "run" / "t.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_msg((1, host), (1, dev)))
    monkeypatch.setattr(harness, "OUT", tmp_path)
    return SimpleNamespace(trace=object())


@pytest.mark.parametrize("ops, ms", [(SCAN + WRITE, 600 / 1e6),
                                     (SCAN, None)],
                         ids=["scope", "no_scope"])
def test_cache_write_ms_per_decode_step(tmp_path, monkeypatch, ops, ms):
    """Self time under ``cache_write`` per run of the step; nothing from
    a program without the scope, or from an untraced run."""
    read = harness.metric_reader("cache_write_ms.serve").read
    got = read(_run(tmp_path, monkeypatch, ops))
    assert got is None if ms is None else got == pytest.approx(ms)
    assert read(SimpleNamespace(trace=None)) is None
