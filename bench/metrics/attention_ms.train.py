"""Device ms per train step of the ops under the ``attention`` scope,
by self time, from the raw trace."""
from bench import scopes


def read(drv):
    return scopes.scope_ms(drv, r"train_step", "attention")
