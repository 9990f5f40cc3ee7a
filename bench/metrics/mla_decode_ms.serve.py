"""Device ms per decode step under the program's ``mla_decode`` scope
(projections, the latent write, the absorbed attention), from the raw
trace."""
from bench import mla_moe_readers as r


def read(drv):
    return r.scope_ms(drv, (r.MLA,))
