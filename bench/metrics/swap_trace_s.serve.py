"""Seconds of jaxpr tracing and lowering to MLIR per deploy, from the
program's ``serve.rebuild`` record of the deployed sampler."""
from bench import records


def read(drv):
    engine = getattr(drv, "engine", None)
    return records.swap_trace_s(drv, getattr(engine, "spans", None),
                                "sampler")
