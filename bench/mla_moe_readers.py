"""Arithmetic the latent-attention MoE readers in ``metrics/`` share.

Each reads the decode step (``serve_step``) of a traced run: device time
by the program's own scopes (``mla_decode``; ``moe_router``,
``moe_experts``, ``moe_shared``), the positions the traced steps attended
to (``drv.traced_positions()``) and the tokens the recorded steps routed
to each expert (``drv.expert_loads``, [MoE layers, E] per step). A run
without them (untraced, another driver, or a program without the scopes
or the counter) gives None, and the metric is left out.
"""
from __future__ import annotations

import statistics
from typing import Optional

from bench import mla_moe_counts as counts
from bench import scopes

MLA = "mla_decode"
MOE = ("moe_router", "moe_experts", "moe_shared")


def _step(drv):
    if not hasattr(drv, "traced_positions"):
        return None
    return scopes.traced_step(drv, r"serve_step")


def scope_ms(drv, names) -> Optional[float]:
    """Device ms per decode step under ``names`` (innermost of the
    latent-attention and MoE scopes), None where no op carries them."""
    st = _step(drv)
    if st is None:
        return None
    by = st.by_scope((MLA,) + MOE)
    secs = sum(by[n] for n in names)
    return st.per_run_ms(secs) if secs > 0 else None


def step_ms(drv) -> Optional[float]:
    st = _step(drv)
    return None if st is None else st.per_run_ms(st.module_s)


def mean_live(drv) -> Optional[float]:
    """Positions each traced step's attention reads (the new one
    included), mean over the traced steps."""
    pos = drv.traced_positions() if hasattr(drv, "traced_positions") else []
    return statistics.fmean(p + 1 for p in pos) if pos else None


def loads(drv):
    return getattr(drv, "expert_loads", None) or None


def hbm_share(nbytes: float, ms: float, drv) -> float:
    return 100.0 * nbytes / (ms / 1e3) / drv.cell.peak["hbm_bytes_per_s"]


def mla_hbm_share(drv) -> Optional[float]:
    ms, live = scope_ms(drv, (MLA,)), mean_live(drv)
    if ms is None or live is None:
        return None
    return hbm_share(counts.mla_decode_bytes(drv.m, drv.B, live), ms, drv)


def expert_hbm_share(drv) -> Optional[float]:
    ms, ld = scope_ms(drv, MOE), loads(drv)
    if ms is None or ld is None:
        return None
    distinct = statistics.fmean(float((x > 0).sum()) for x in ld)
    return hbm_share(counts.moe_decode_bytes(drv.m, distinct), ms, drv)


def load_imbalance(drv) -> Optional[float]:
    """The busiest expert's tokens over the mean, per MoE layer and step,
    averaged."""
    ld = loads(drv)
    if ld is None:
        return None
    return statistics.fmean(float(layer.max()) / float(layer.mean())
                            for x in ld for layer in x)


def decode_mfu(drv) -> Optional[float]:
    ms, live = step_ms(drv), mean_live(drv)
    if ms is None or live is None:
        return None
    flops = counts.decode_step_flops(drv.m, drv.B, live)
    return 100.0 * flops / (ms / 1e3) / drv.cell.peak["bf16_flops_per_s"]
