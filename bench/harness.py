"""The benchmark harness: one run of one cell.

Everything that belongs to one cell is found by name from
``BENCHMARK.json``: the cell names its configuration
(``configs/<config>.json``) and its traffic mix
(``traffic/<traffic>.json``, which names the generic driver in
``drivers/`` that runs it); the limits of its correctness check are in
``limits/<cell>.json``; each per-layer metric is read by
``metrics/<metric>.py``. Adding a cell, a configuration, a mix or a
metric adds files and entries and edits none.

A run: set-up (build the program, make its weights from the seed, warm
every shape the cell uses, run the steps the check compares), the
measured window of ``--seconds``, the device's peak memory, then the
program's state is freed and the plain reference decides ``correct``.
With ``--trace 1`` a part of the window is traced and the per-layer
metrics are printed instead of the end-to-end ones.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"

import jax  # noqa: E402

from bench import trace_reduce  # noqa: E402


class BenchError(RuntimeError):
    """A run that cannot give a result: exit non-zero, print none."""


# ---------------------------------------------------------------------------
# Files found by name
# ---------------------------------------------------------------------------

def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict:
    return load_json(ROOT / "BENCHMARK.json")


def published_sizes(cfg: Dict) -> Dict:
    """The model's sizes as the published config gives them."""
    p = cfg["published"]
    heads = p["num_attention_heads"]
    return {
        "layers": p["num_hidden_layers"], "d_model": p["hidden_size"],
        "heads": heads, "kv_heads": p["num_key_value_heads"],
        "head_dim": p.get("head_dim", p["hidden_size"] // heads),
        "d_ff": p["intermediate_size"], "vocab": p["vocab_size"],
        "eps": p["rms_norm_eps"], "rope_theta": p["rope_theta"],
        "tied": p["tie_word_embeddings"], "qk_norm": cfg["qk_norm"],
    }


# program ModelConfig field for each size
PROGRAM_FIELDS = {"layers": "num_layers", "d_model": "d_model",
                  "heads": "num_heads", "kv_heads": "num_kv_heads",
                  "head_dim": "head_dim", "d_ff": "d_ff",
                  "vocab": "vocab_size", "eps": "norm_eps",
                  "rope_theta": "rope_theta", "tied": "tie_embeddings",
                  "qk_norm": "qk_norm"}


def program_model(cfg: Dict):
    """The program's preset with the configuration's overrides, checked
    against the published sizes: a preset that disagrees is an error,
    not a different model under the same name."""
    from repro.configs import get_config
    mc = dataclasses.replace(get_config(cfg["preset"]), **cfg["overrides"])
    m = published_sizes(cfg)
    wrong = {k: (getattr(mc, f) if k != "head_dim" else mc.hd(), m[k])
             for k, f in PROGRAM_FIELDS.items()
             if (getattr(mc, f) if k != "head_dim" else mc.hd()) != m[k]}
    if wrong:
        raise BenchError(f"preset {cfg['preset']} differs from the published "
                         f"config (program, published): {wrong}")
    if mc.param_dtype != cfg["dtypes"]["params"] or \
            mc.dtype != cfg["dtypes"]["compute"]:
        raise BenchError(f"preset dtypes {mc.param_dtype}/{mc.dtype} are not "
                         f"the configuration's {cfg['dtypes']}")
    return mc


@dataclasses.dataclass
class Cell:
    name: str
    config: Dict
    traffic: Dict
    limits: Dict
    m: Dict
    peak: Dict
    chips: int


def load_cell(name: str, *, config: Optional[Dict] = None,
              traffic: Optional[Dict] = None,
              limits: Optional[Dict] = None) -> Cell:
    spec = benchmark()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = config or load_json(BENCH / "configs" / f"{w['config']}.json")
    tr = traffic or load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    lim = limits or load_json(BENCH / "limits" / f"{name}.json")
    return Cell(name, cfg, tr, lim, published_sizes(cfg), {}, w["chips"])


def peak_for(kind: str) -> Dict:
    peaks = load_json(BENCH / "peaks.json")
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return peaks[kind]


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver_module(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


# ---------------------------------------------------------------------------
# What the run records besides its own timings
# ---------------------------------------------------------------------------

class CompileLog:
    """Backend compiles as ``jax.monitoring`` reports them: (end time on
    ``time.perf_counter``, seconds)."""

    def __init__(self) -> None:
        self.events: List[Tuple[float, float]] = []
        self._lock = threading.Lock()

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.events.append((time.perf_counter(), duration))

    def __enter__(self) -> "CompileLog":
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc: Any) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)

    def between(self, t0: float, t1: float) -> List[float]:
        with self._lock:
            return [d for t, d in self.events if t0 <= t <= t1]


def span(name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    return jax.profiler.TraceAnnotation(f"bench.{name}")


class Tracer:
    """Traces one part of the window into ``.bench_out/trace``; does
    nothing when the run is not traced."""

    def __init__(self, on: bool) -> None:
        self.on = on
        self.dir = OUT / "trace"
        self.started = self.stopped = None
        self._ann = None

    def start(self) -> None:
        if not self.on or self.started is not None:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._ann = span("window")
        self._ann.__enter__()
        self.started = time.perf_counter()

    def stop(self) -> None:
        if self.started is None or self.stopped is not None:
            return
        self.stopped = time.perf_counter()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduced(self) -> Optional[trace_reduce.Reduced]:
        if self.started is None:
            return None
        path = trace_reduce.newest_xplane(str(self.dir))
        if path is None:
            raise BenchError("the profiler wrote no trace")
        tr = trace_reduce.from_xplane(path)
        trace_reduce.save(tr, str(OUT / "last_trace.json.gz"))
        return trace_reduce.reduce(tr)


def deploy_times(traffic: Dict, seconds: float) -> List[float]:
    """Seconds into the window at which the mix deploys new code:
    ``first_s``, then every ``every_s``, while inside the window."""
    d = traffic.get("deploys")
    if not d:
        return []
    n = max(0, math.ceil((seconds - d["first_s"]) / d["every_s"]))
    return [d["first_s"] + k * d["every_s"] for k in range(n)]


def check_device(chips: int, allow_cpu: bool = False):
    devs = jax.devices()
    if allow_cpu:
        return devs
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def memory_stats() -> Dict:
    return jax.devices()[0].memory_stats() or {}


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, allow_cpu: bool = False,
             cell: Optional[Cell] = None, log=print) -> Dict:
    """Runs the cell once and returns the result line's object."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = cell or load_cell(name)
    devs = check_device(cell.chips, allow_cpu)
    dev = devs[0]
    cell.peak = peak_for(dev.device_kind) if not allow_cpu else \
        cell.peak or peak_for("TPU v5 lite")
    spec = benchmark()
    drv_mod = driver_module(cell.traffic["driver"])
    tracer = Tracer(trace)
    with CompileLog() as clog:
        drv = drv_mod.Driver(cell, seed, clog)
        drv.setup()
        drv.window(seconds, tracer)
        tracer.stop()
    setup_s = drv.t0 - t_start
    stats = memory_stats()
    log(f"memory_stats: {json.dumps(stats)}", file=sys.stderr)
    # A TPU holds each program's temporaries in a reservation of its own,
    # which peak_bytes_in_use leaves out; the reservation is kept once
    # made, so the two peaks are held together.
    memory = int(stats.get("peak_bytes_in_use", 0)) + \
        int(stats.get("peak_bytes_reserved", 0))
    reduced = tracer.reduced()
    drv.trace = reduced
    drv.trace_span = (tracer.started, tracer.stopped) if reduced else None
    n_compiles = len(clog.between(drv.t0, drv.t1))
    log(f"backend compiles in the window: {n_compiles}; deploys in the "
        f"window: {len(drv.deploys)}", file=sys.stderr)
    e2e = dict(drv.end_to_end(), setup_s=setup_s)
    per_layer = {}
    if trace:
        for mt in spec["per_layer"]:
            if name not in mt.get("workloads", [name]):
                continue
            v = metric_reader(mt["name"]).read(drv)
            if v is not None:
                per_layer[mt["name"]] = {"value": v, "unit": mt["unit"]}
    drv.release()
    gc.collect()
    readings = drv.check()
    for c, (v, lim) in readings.items():
        if lim is None:
            log(f"reading {c} = {v!r} (not compared)", file=sys.stderr)
    checks = {c: r for c, r in readings.items() if r[1] is not None}
    for c, (v, lim) in checks.items():
        log(f"check {c} = {v!r} limit {lim!r}", file=sys.stderr)
    correct = (all(v <= lim for v, lim in checks.values())
               and drv.failed == 0 and all(math.isfinite(v)
                                           for v, _ in checks.values()))
    if trace:
        metrics = per_layer
    else:
        metrics = {}
        for mt in spec["end_to_end"]:
            if name in mt.get("workloads", [name]) and mt["name"] in e2e:
                metrics[mt["name"]] = {"value": e2e[mt["name"]],
                                       "unit": mt["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory}
    out = {"correct": bool(correct), "attempted": drv.attempted,
           "failed": drv.failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = {"device_ops": reduced.top_ops(10),
                            "idle_gaps": [list(g) for g in reduced.gaps[:10]]}
    out["checks"] = {c: {"value": v, "limit": lim}
                     for c, (v, lim) in checks.items()}
    return out
