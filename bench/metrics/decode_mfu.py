"""The whole decode step's share of the chip's peak (the larger of the
FLOP and the byte bound), from the trace."""
from bench import metric_lib


def read(drv):
    return metric_lib.decode_mfu(drv)
