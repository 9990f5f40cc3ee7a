"""Share of the traced window in which no operation ran on the device."""
from bench import metric_lib


def read(drv):
    return metric_lib.idle_share(drv)
