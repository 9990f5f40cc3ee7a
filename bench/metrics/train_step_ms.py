"""Device time of one run of the jitted train step, from the trace."""
from bench import metric_lib


def read(drv):
    return metric_lib.module_ms(drv, r"train_step")
