"""Readings that set the limits of a cell's correctness check.

    python3 bench/control.py --workload <cell> --seeds <n> --controls <k> \
        --seconds <s> [--first-seed <n>]

For ``--seeds`` seeds in one process: the program's set-up and a short
window at the cell's own sizes and load, then the numbers the run
compares (the lower readings). For the first ``--controls`` of them
also the control, the reference computed in float8 (e4m3, one absmax
scale per tensor) in the program's place, and for training cells the
reference with half of each batch left out and the mean taken over the
rest: the upper readings. Prints one JSON line per seed and writes them
to ``chiprun_out/control_<cell>.jsonl``. Not part of a benchmark run.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
(ROOT / ".jax_cache").mkdir(exist_ok=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seed: int, seconds: float, control: bool) -> dict:
    from bench import harness
    from bench.drivers import train as train_driver
    drv = harness.driver_module(cell.traffic["driver"]).Driver(
        cell, seed, harness.CompileLog())
    drv.setup()
    drv.window(seconds, harness.Tracer(False))
    drv.release()
    gc.collect()
    out = {"seed": seed, "control": "fp8 e4m3: matmul operands and residual"}
    if cell.traffic["driver"] == "train":
        ref = drv.reference()
        out["program"] = {k: v for k, (v, _) in train_driver.compare(
            drv.readings(), ref, {}).items()}
        if control:
            out["control_fp8"] = {k: v for k, (v, _) in train_driver.compare(
                drv.reference("fp8"), ref, {}).items()}
            half = range(drv.B // 2)
            out["fault_half_batch"] = {k: v for k, (v, _) in
                                       train_driver.compare(
                drv.reference(rows_used=half), ref, {}).items()}
    else:
        picks = drv.sample()
        out["program"] = {"logit_gap": drv.widest_gap(picks)}
        out["failed"] = drv.failed
        if control:
            out["control_fp8"] = {"logit_gap": drv.widest_gap(picks, "fp8")}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--first-seed", type=int, default=2_000_000_011)
    args = ap.parse_args()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.check_device(cell.chips)
    cell.peak = harness.peak_for(jax.devices()[0].device_kind)
    out_path = ROOT / "chiprun_out" / f"control_{args.workload}.jsonl"
    out_path.parent.mkdir(exist_ok=True)
    with open(out_path, "a") as f:
        for i in range(args.seeds):
            t = time.perf_counter()
            r = readings(cell, args.first_seed + 7919 * i, args.seconds,
                         i < args.controls)
            r["seconds"] = time.perf_counter() - t
            print(json.dumps(r), flush=True)
            f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
