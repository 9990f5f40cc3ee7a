"""Loop-aware HLO cost analysis and the three-term roofline."""
from repro.analysis.hlo import collective_bytes, parse_collectives
from repro.analysis.roofline import RooflineTerms

__all__ = ["RooflineTerms", "collective_bytes", "parse_collectives"]
