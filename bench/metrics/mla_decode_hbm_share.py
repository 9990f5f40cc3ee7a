"""The bytes the absorbed latent attention must move in a decode step
(each row's latent cache up to its position, every layer, and the
attention weights) over its device time at the HBM peak, in %."""
from bench import mla_moe_readers as r


def read(drv):
    return r.mla_hbm_share(drv)
