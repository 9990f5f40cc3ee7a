"""From a profiler trace to device busy time, device ops and idle gaps.

``from_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into a plain dict (planes -> lines -> events), keeping of each event
its name, start, duration and the ``hlo_module`` / ``hlo_op`` stats.
``reduce`` works on that dict, so the tests can run it on a small
recorded trace without the profiler.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line gives the
operations, their ``XLA Modules`` line the programs. The traced window
is the host span ``bench.window`` that the harness opens; a gap in
which no operation runs is labelled by the innermost host span that
covers its middle.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
KEEP_STATS = ("hlo_module", "hlo_op")


def newest_xplane(log_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def from_xplane(path: str) -> Dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = []
            for e in line.events:
                if not device and (e.name.startswith("$") or e.duration_ns <= 0):
                    continue       # python-tracer frames and instants
                stats = {}
                if device:
                    for k, v in e.stats:
                        if k in KEEP_STATS:
                            stats[k] = v
                name = op_name(e.name) if device else e.name
                evs.append([name, float(e.start_ns), float(e.duration_ns),
                            stats])
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def save(trace: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def op_name(name: str) -> str:
    """``%rmsnorm_pallas.3 = bf16[...] custom-call(...)`` -> the HLO
    instruction's name, ``rmsnorm_pallas.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def module_name(name: str) -> str:
    """``jit_train_step(12)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


class Reduced:
    """One traced window, reduced. Times are in seconds."""

    def __init__(self, busy_s: float, window_s: float, ops: List,
                 modules: List, gaps: List, n_devices: int):
        self.busy_s = busy_s            # mean over the device planes
        self.window_s = window_s
        self.ops = ops                  # (module, op, start_ns, dur_ns)
        self.modules = modules          # (module, start_ns, dur_ns)
        self.gaps = gaps                # (label, seconds), longest first
        self.n_devices = n_devices

    def module_times(self, pattern: str) -> List[float]:
        """Seconds of each run of the programs whose name matches."""
        rx = re.compile(pattern)
        return [d / 1e9 for m, _, d in self.modules if rx.search(m)]

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` ops with most self time (time in which no op nested
        in them, such as the body of a loop, runs), by module/op."""
        tot: Dict[str, float] = {}
        for mod, op, secs in self_times(self.ops):
            key = f"{mod}/{op}"
            tot[key] = tot.get(key, 0.0) + secs
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def self_times(ops: List) -> List[Tuple[str, str, float]]:
    """(module, op, seconds) with the time of nested ops taken out."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][2], -ops[i][3]))
    own = [op[3] for op in ops]
    stack: List[int] = []
    for i in order:
        s = ops[i][2]
        while stack and ops[stack[-1]][2] + ops[stack[-1]][3] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i][3]
        stack.append(i)
    return [(ops[i][0], ops[i][1], max(own[i], 0.0) / 1e9)
            for i in range(len(ops))]


def reduce(trace: Dict) -> Reduced:
    host_spans = []
    devices = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            devices.append(plane)
            continue
        for line in plane["lines"]:
            for name, s, d, _ in line["events"]:
                host_spans.append((name, s, s + d))
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    evs = [(s, s + d) for p in devices for ln in p["lines"]
           for _, s, d, _ in ln["events"]]
    if not evs:
        raise ValueError("no operation ran on the device in the trace")
    win = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if win and any(s < win[0][1] and e > win[0][0] for s, e in evs):
        lo, hi = win[0]
    else:       # no host window, or host and device clocks do not meet
        lo, hi = min(s for s, _ in evs), max(e for _, e in evs)
    ops, modules, busy_total, gaps = [], [], 0.0, []
    for plane in devices:
        intervals, runs = [], []
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        for name, s, d, _ in lines.get(MODULES_LINE, []):
            runs.append((s, s + d, module_name(name)))
            if _clip(s, s + d, lo, hi) == (s, s + d):    # whole runs only
                modules.append((module_name(name), s, d))
        runs.sort()
        starts = [r[0] for r in runs]
        for name, s, d, st in lines.get(OPS_LINE, []):
            c = _clip(s, s + d, lo, hi)
            if c is None:
                continue
            k = bisect.bisect_right(starts, s) - 1
            mod = st.get("hlo_module") or (
                runs[k][2] if k >= 0 and runs[k][1] >= s else "")
            ops.append((module_name(mod), op_name(st.get("hlo_op", name)),
                        c[0], c[1] - c[0]))
            intervals.append(c)
        busy = union(intervals)
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((label(host_spans, (s + e) / 2), (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(busy_total / len(devices) / 1e9, (hi - lo) / 1e9, ops,
                   modules, gaps, len(devices))


def label(host_spans: List, t: float) -> str:
    """What the host was doing at ``t``: the innermost of the benchmark's
    own spans (``bench.*``) over it, then the outermost span of the
    program or the runtime inside that one (a compile if there is one,
    else a dispatch, a transfer)."""
    around = [(e - s, n) for n, s, e in host_spans
              if s <= t <= e and n != WINDOW_SPAN]
    ours = [a for a in around if a[1].startswith("bench.")]
    inner = min(ours) if ours else (float("inf"), "")
    others = [a for a in around
              if not a[1].startswith("bench.") and a[0] < inner[0]]
    compiles = [a for a in others if "compile" in a[1].lower()]
    parts = [inner[1]] if ours else []
    if compiles or others:
        parts.append(max(compiles or others)[1])
    return ">".join(parts) or "no host span"
