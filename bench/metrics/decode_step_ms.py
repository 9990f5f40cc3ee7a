"""Device time of one run of the jitted decode step, from the trace."""
from bench import metric_lib


def read(drv):
    return metric_lib.module_ms(drv, r"serve_step")
