"""Seconds of jaxpr tracing and lowering to MLIR per deploy, from the
program's ``train.rebuild`` record of the deployed ``train_loss``."""
from bench import records


def read(drv):
    step = getattr(getattr(drv, "trainer", None), "step", None)
    return records.swap_trace_s(drv, getattr(step, "spans", None),
                                "train_loss")
