"""Benchmark harness: one module per paper table / system claim.

    PYTHONPATH=src python -m benchmarks.run [--only <name>]

Prints ``name,us_per_call,derived`` CSV plus JSON mirrors under
experiments/: the full run in ``bench.json`` and one
``BENCH_<suite>.json`` per suite that ran (e.g. ``BENCH_fabric.json``
for the transport-fabric numbers), so per-subsystem perf trajectories
are diffable across PRs.

All suites run in this one process. The suites that spawn TCP fleets
(``bench_fleet``, ``bench_fabric``) place their children on the host CPU
(``repro.launch.fleet_proc``), so no child reaches for a chip that this
process may hold.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os

from repro.launch.cache import setup_compile_cache

SUITES = ("bench_replacement", "bench_fleet", "bench_fabric",
          "bench_swap_overhead", "bench_kernels")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default="experiments/bench.json")
    args = ap.parse_args()
    setup_compile_cache()

    rows = []
    by_suite = {}

    def make_report(suite):
        def report(name, us, derived=""):
            row = {"name": name, "us_per_call": us, "derived": derived}
            rows.append(row)
            by_suite.setdefault(suite, []).append(row)
            print(f"{name},{us:.1f},{derived}", flush=True)
        return report

    print("name,us_per_call,derived")
    for suite in SUITES:
        if args.only and args.only not in suite:
            continue
        mod = importlib.import_module(f"benchmarks.{suite}")
        mod.main(make_report(suite))
    if args.json:
        out_dir = os.path.dirname(args.json) or "."
        os.makedirs(out_dir, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
        for suite, suite_rows in by_suite.items():
            tag = suite.removeprefix("bench_")
            path = os.path.join(out_dir, f"BENCH_{tag}.json")
            # merge by name: refresh the rows this run produced, keep the
            # ones it did not (e.g. the soak rows tests/test_soak.py
            # records into BENCH_fabric.json — a light run must not
            # clobber the heavyweight trajectory)
            merged = {}
            if os.path.exists(path):
                with open(path) as f:
                    merged = {r["name"]: r for r in json.load(f)}
            for r in suite_rows:
                merged[r["name"]] = r
            with open(path, "w") as f:
                json.dump(list(merged.values()), f, indent=1)


if __name__ == "__main__":
    main()
