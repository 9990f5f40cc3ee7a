"""The bytes of the distinct routed experts a decode step's routing hit
(from the program's per-step expert counter), the shared experts and the
routers, over the MoE scopes' device time at the HBM peak, in %."""
from bench import mla_moe_readers as r


def read(drv):
    return r.expert_hbm_share(drv)
