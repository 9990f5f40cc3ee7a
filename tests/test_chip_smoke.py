"""chip_smoke.py's phases at the reduced config on the CPU, and its
refusal to report anything when no TPU is there."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_phase_swaps_loss(chip_smoke):
    r = chip_smoke.train_phase(reduced=True, batch=2, seq=32, steps=5,
                               swap_after=2)
    assert len(r["losses"]) == 5
    assert r["rebuilds"] == 2          # builtin loss, then the deploy
    assert r["losses"][0] == pytest.approx(r["ref_loss"], rel=2e-2)


def test_serve_phase_swaps_and_rolls_back_sampler(chip_smoke):
    r = chip_smoke.serve_phase(reduced=True, batch=2, prompt_len=8,
                               tokens=6, max_seq=32)
    v1, v2 = r["sampler_md5s"]
    assert v1 != v2
    assert r["swap_after_token"] == 4
    assert r["rebuilds"] == 2          # greedy step, temperature step


def test_fleet_phase_runs_readme_flow(chip_smoke):
    r = chip_smoke.fleet_phase(n_clients=3)
    assert r["client_array_platform"] == "cpu"
    assert r["iterations"] >= 3


def test_phase_failure_is_raised(chip_smoke):
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.train_phase(reduced=True, steps=3, swap_after=2)


def test_main_refuses_cpu_backend(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails_without_ok_line(tmp_path):
    """Outside a checkout (the script and nothing else of the repo) it
    fails before reaching JAX's backend, and reports nothing."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
