"""Device ms per decode step under the program's ``moe_router``,
``moe_experts`` and ``moe_shared`` scopes, from the raw trace."""
from bench import mla_moe_readers as r


def read(drv):
    return r.scope_ms(drv, r.MOE)
