"""Arithmetic for the per-layer readers that read what the program
records about itself: the span histograms in a ``Metrics`` and the
rebuild records in a ``SpanRecorder`` (``repro.core.telemetry``).

A program without them (an older checkout) gives None, and the metric
is left out."""
from __future__ import annotations

import statistics
from typing import Optional


def steady_mean(metrics, name: str) -> Optional[float]:
    """Mean of the ``name`` histogram without its largest value, or None
    where there is none. A span's first run in a process can compile
    (the first batch compiles its slicing ops: 152 ms against 15-18 on a
    v5e), which would weigh on a mean over some twenty steps."""
    if metrics is None:
        return None
    h = metrics.histograms(name).get(name)
    if not h or h["count"] < 2:
        return None
    return (h["sum"] - h["max"]) / (h["count"] - 1)


def swap_trace_s(drv, spans, slot: str) -> Optional[float]:
    """Per deploy of the window, the trace plus lower seconds of the
    rebuild records whose ``slot`` md5 is the deploy's; mean over the
    deploys that have one."""
    if spans is None:
        return None
    recs = [s.get("attrs", {}) for s in spans.drain()]
    per = []
    for d in drv.deploys:
        hit = [a for a in recs if a.get("md5s", {}).get(slot) == d["md5"]]
        if hit:
            per.append(sum(a["trace_s"] + a["lower_s"] for a in hit))
    return statistics.fmean(per) if per else None
