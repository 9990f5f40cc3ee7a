"""The harness finds every cell, configuration, mix, limit and metric by
name, and refuses to run where it cannot give a device result."""
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = harness.benchmark()
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_benchmark_json_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in CELLS
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in e2e for m in SPEC["per_layer"])
    assert "setup_s" in e2e


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = harness.load_cell(cell)
    assert harness.driver_module(c.traffic["driver"]).Driver
    assert c.limits and all(v > 0 for v in c.limits.values())
    reports = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]
               if cell in m.get("workloads", [cell])]
    assert "setup_s" in reports and len(reports) >= 3


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_metric_reader_loads(metric):
    assert callable(harness.metric_reader(metric).read)


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_config_matches_program_preset(config):
    entry = {c["name"]: c for c in SPEC["configs"]}[config]
    cfg = harness.load_json(harness.ROOT / entry["file"])
    assert cfg["source"] == entry["source"]
    mc = harness.program_model(cfg)
    assert mc.name == cfg["preset"]


def test_preset_that_disagrees_is_refused():
    cfg = harness.load_json(harness.BENCH / "configs" / "qwen3-0.6b.json")
    cfg["overrides"] = {}           # the preset's eps is 1e-5, published 1e-6
    with pytest.raises(harness.BenchError, match="eps"):
        harness.program_model(cfg)


def test_unknown_device_kind_is_an_error():
    assert harness.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="peaks.json"):
        harness.peak_for("TPU v99")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(2 ** 31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    for d in SPEC["paths"]:
        shutil.copytree(harness.ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
