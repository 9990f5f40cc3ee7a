"""Three-term roofline of a compiled program.

TPU v5e per chip: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
The compiled module is the per-device SPMD program, so cost_analysis
flops/bytes and the parsed collective bytes are already per-chip:

    compute    = flops / 197e12
    memory     = bytes_accessed / 819e9
    collective = wire_bytes / 50e9

The dominant term approximates the step's lower-bound time on one chip;
MODEL_FLOPS/HLO_FLOPs (6ND over per-chip-flops x chips) measures how
much of the compiled compute is "useful" (remat/dispatch/padding waste).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

PEAK_FLOPS = 197e12       # bf16 / chip
HBM_BW = 819e9            # B/s / chip
ICI_BW = 50e9             # B/s / link


@dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_chip: float
    bytes_per_chip: float
    fused_bytes_per_chip: float
    wire_bytes_per_chip: float
    model_flops: float            # 6*N*D (active params for MoE)
    peak_memory_bytes: float      # from memory_analysis
    collective_detail: Dict[str, Any]

    @property
    def t_compute(self) -> float:
        return self.flops_per_chip / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_per_chip / HBM_BW

    @property
    def t_memory_fused(self) -> float:
        """Memory term under perfect elementwise fusion (TPU-fusion proxy;
        the CPU-compiled HLO fuses less than TPU XLA would)."""
        return self.fused_bytes_per_chip / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.wire_bytes_per_chip / ICI_BW

    @property
    def bottleneck(self) -> str:
        """Dominant term. The memory term here is the fused-bytes figure:
        the raw CPU-HLO traffic includes XLA:CPU artifacts (hoisted
        full-buffer dtype converts, unfused softmax chains) that the TPU
        pipeline fuses away; both figures are recorded."""
        terms = {"compute": self.t_compute, "memory": self.t_memory_fused,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory_fused, self.t_collective)

    @property
    def useful_flops_fraction(self) -> float:
        total_hlo = self.flops_per_chip * self.chips
        return self.model_flops / total_hlo if total_hlo else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the dominant-term bound that is useful model
        compute: (model_flops/chips/peak) / t_bound. This is the MFU the
        step would achieve if it ran exactly at the roofline bound."""
        if self.t_bound == 0:
            return 0.0
        t_useful = self.model_flops / self.chips / PEAK_FLOPS
        return t_useful / self.t_bound

    def as_dict(self) -> Dict[str, Any]:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "flops_per_chip": self.flops_per_chip,
            "bytes_per_chip": self.bytes_per_chip,
            "fused_bytes_per_chip": self.fused_bytes_per_chip,
            "wire_bytes_per_chip": self.wire_bytes_per_chip,
            "model_flops": self.model_flops,
            "peak_memory_bytes": self.peak_memory_bytes,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_memory_fused_s": self.t_memory_fused,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_fraction": self.useful_flops_fraction,
            "roofline_fraction": self.roofline_fraction,
            "collectives": self.collective_detail,
        }
