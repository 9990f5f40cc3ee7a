"""Device ms per decode step under the program's ``cache_write`` scope:
the one write, after the layer scan, of each layer's new cache rows into
the stacked cache, from the raw trace. A program without the scope gives
nothing."""
from bench import scopes


def read(drv):
    st = scopes.traced_step(drv, r"serve_step")
    if st is None:
        return None
    secs = st.by_scope(("cache_write",))["cache_write"]
    return st.per_run_ms(secs) if secs > 0 else None
