"""Host milliseconds the training loop spends building each step's
batch: the mean of the program's ``train.batch`` span histogram, less
its largest value (the first batch, which compiles)."""
from bench import records


def read(drv):
    loop = getattr(getattr(drv, "trainer", None), "loop", None)
    return records.steady_mean(getattr(loop, "metrics", None), "train.batch")
