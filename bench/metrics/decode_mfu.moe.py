"""The whole decode step's FLOPs (2 per active weight per token, and the
absorbed attention over the positions read) over its device time at the
bf16 peak, in %."""
from bench import mla_moe_readers as r


def read(drv):
    return r.decode_mfu(drv)
