"""The per-layer readers of what the program records about itself (its
span histograms and rebuild records) give numbers through a whole run
on the CPU at a tiny size; the readers of the device trace stay silent
where there is no trace."""
import pytest

from bench import harness

from tiny import tiny_cell

SEED = 2 ** 31 + 303


class NoTrace(harness.Tracer):
    """Records nothing, so that a run asked to trace reads every
    per-layer metric on the CPU, which gives no device trace."""

    def __init__(self, on):
        super().__init__(False)


@pytest.mark.parametrize("cell, reads, silent", [
    ("smollm-train-steady", ["batch_ms"],
     ["attention_ms.train", "rmsnorm_ms", "train_step_ms"]),
    ("smollm-train-swap", ["swap_trace_s.train", "swap_compile_s.train"], []),
    ("qwen3-serve-swap", ["swap_trace_s.serve", "swap_compile_s.serve"], []),
])
def test_program_readers_through_run_cell(monkeypatch, cell, reads, silent):
    monkeypatch.setattr(harness, "Tracer", NoTrace)
    out = harness.run_cell(cell, SEED, 1.0, True, allow_cpu=True,
                           cell=tiny_cell(cell), log=lambda *a, **k: None)
    got = out["metrics"]
    for name in reads:
        assert 0 < got[name]["value"] < 60, (name, got[name])
    for name in silent:
        assert name not in got
    assert out["correct"]
