"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
initialization).

Every mesh is built with ``AxisType.Auto`` axes: the model code places
arrays with sharding constraints and leaves propagation to the
compiler (GSPMD). ``jax.make_mesh`` defaults to ``Explicit`` axes,
under which a reshape that merges a sharded dim (``ssm_decode``'s
``[B, H, P] -> [B, 1, H*P]``) is refused at trace time.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_mesh_like(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    if multi_pod:
        return make_mesh_like((2, 16, 16), ("pod", "data", "model"))
    return make_mesh_like((16, 16), ("data", "model"))


def make_host_mesh(data: Optional[int] = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = jax.device_count()
    if data is None:
        data = n // model
    return make_mesh_like((data, model), ("data", "model"))
