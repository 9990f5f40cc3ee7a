"""Arithmetic the per-layer readers in ``metrics/`` share.

Each reader gets the driver of the run that just ended: its records
(``steps`` or ``calls``, ``deploys``), its ``clog`` of backend compiles,
``trace`` (a ``trace_reduce.Reduced``, or None in an untraced run),
``trace_span`` (host times the trace started and stopped) and the
cell (``cell.m`` sizes, ``cell.peak``, ``cell.traffic``). A reader that
finds nothing to read returns None, and the metric is left out.
"""
from __future__ import annotations

import statistics
from typing import Optional

from bench import flops

def compile_s_per_deploy(drv) -> Optional[float]:
    """Backend-compile seconds between each deploy and its effect, mean
    over the window's deploys."""
    per = [sum(drv.clog.between(d["t_deploy"], d["t_effect"]))
           for d in drv.deploys if d["t_effect"] is not None]
    return statistics.fmean(per) if per else None


def idle_share(drv) -> Optional[float]:
    tr = drv.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def module_ms(drv, pattern: str) -> Optional[float]:
    if drv.trace is None:
        return None
    times = drv.trace.module_times(pattern)
    return 1e3 * statistics.fmean(times) if times else None


def train_mfu(drv) -> Optional[float]:
    """Model FLOPs of the window's steps that compiled nothing, over
    their wall time, as a share of the bf16 peak."""
    steps = [s for s in drv.steps if not drv.clog.between(s["t0"], s["t1"])]
    if not steps:
        return None
    per_tok = flops.train_flops_per_token(drv.cell.m, drv.S)
    work = sum(s["tokens"] for s in steps) * per_tok
    wall = sum(s["t1"] - s["t0"] for s in steps)
    return 100.0 * work / wall / drv.cell.peak["bf16_flops_per_s"]


def decode_mfu(drv) -> Optional[float]:
    """The decode step's least time (the larger of its least FLOPs over
    the FLOP peak and its least bytes over the HBM peak) as a share of
    its device time, at the mean live length of the traced steps."""
    ms = module_ms(drv, r"serve_step")
    if ms is None or drv.trace_span is None:
        return None
    lo, hi = drv.trace_span
    live = [drv.P + i + 1 for c in drv.calls
            for i, t in enumerate(c["arrivals"]) if lo <= t <= hi]
    if not live:
        return None
    mean_live = statistics.fmean(live)
    m, peak = drv.cell.m, drv.cell.peak
    least, _ = flops.least_seconds(flops.decode_step_flops(m, drv.B, mean_live),
                                   flops.decode_step_bytes(m, drv.B, mean_live),
                                   peak)
    return 100.0 * least / (ms / 1e3)
