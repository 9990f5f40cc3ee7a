"""Model FLOPs per second of the steps that compiled nothing, as a share
of the chip's bf16 peak."""
from bench import metric_lib


def read(drv):
    return metric_lib.train_mfu(drv)
