"""The busiest expert's tokens over the mean tokens per expert, per MoE
layer and recorded decode step, averaged (from the program's per-step
expert counter)."""
from bench import mla_moe_readers as r


def read(drv):
    return r.load_imbalance(drv)
