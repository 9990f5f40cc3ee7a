"""Device ms per train step of the Pallas kernel named ``rmsnorm``, by
self time, from the raw trace."""
from bench import scopes


def read(drv):
    return scopes.kernel_ms(drv, r"train_step", "rmsnorm")
