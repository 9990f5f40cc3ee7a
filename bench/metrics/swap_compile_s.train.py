"""Backend-compile seconds per deploy, between the deploy and its
effect (jax.monitoring's backend-compile durations)."""
from bench import metric_lib


def read(drv):
    return metric_lib.compile_s_per_deploy(drv)
