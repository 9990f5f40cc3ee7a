"""``bench/control.py`` at a tiny size on the CPU: the readings the
limits are set from. At the cells' own sizes it runs on the chip; here
it shows that the program reads closer to the reference than the float8
control and the half-batch fault do."""
import math

import pytest

from bench import control, harness
from bench.drivers import serve as serve_driver

from tiny import WIDER_MODEL, tiny_cell


@pytest.mark.parametrize("cell", ["smollm-train-steady", "smollm-train-swap"])
def test_training_control_reads_farther_than_the_program(cell):
    c = tiny_cell(cell)
    c.peak = harness.peak_for("TPU v5 lite")
    r = control.readings(c, 2 ** 31 + 3, 0.5, True)
    for k in ("loss_rel", "grad_rel"):
        assert r["control_fp8"][k] > 2 * r["program"][k], r
        assert r["fault_half_batch"][k] > 10 * r["program"][k], r
    assert all(r["program"][k] <= lim for k, lim in c.limits.items()), r


def test_serving_control_runs():
    c = tiny_cell("qwen3-serve-steady")
    c.peak = harness.peak_for("TPU v5 lite")
    r = control.readings(c, 2 ** 31 + 3, 0.5, True)
    assert r["failed"] == 0
    assert r["program"]["logit_gap"] <= c.limits["logit_gap"]
    assert math.isfinite(r["control_fp8"]["logit_gap"])


def wider_serve_cell(cell):
    c = tiny_cell(cell, WIDER_MODEL)
    c.traffic.update(batch=4, prompt_len=96, new_tokens=24, max_seq=120,
                     check_rows=4)
    if "deploys" in c.traffic:
        c.traffic["deploys"].update(first_s=0.3, every_s=10.0)
    return c


@pytest.mark.parametrize("control_in_place", [False, True])
def test_serving_control_is_not_correct_through_run_cell(control_in_place,
                                                         monkeypatch):
    """The whole run with the float8 control's readings in the program's
    place: at each sampled position the token float8 puts first, read
    under the f32 reference. ``correct`` comes out false, and true for
    the program itself."""
    if control_in_place:
        gap = serve_driver.Driver.widest_gap
        monkeypatch.setattr(serve_driver.Driver, "widest_gap",
                            lambda self, picks, mode="f32":
                            gap(self, picks, "fp8"))
    out = harness.run_cell("qwen3-serve-steady", 2 ** 31 + 17, 1.0, False,
                           allow_cpu=True,
                           cell=wider_serve_cell("qwen3-serve-steady"),
                           log=lambda *a, **k: None)
    assert out["correct"] is not control_in_place, out["checks"]
