"""Mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state.

Every mesh is built with ``AxisType.Auto`` axes: the model code places
arrays with sharding constraints and leaves propagation to the
compiler (GSPMD). ``jax.make_mesh`` defaults to ``Explicit`` axes,
under which a reshape that merges a sharded dim (``ssm_decode``'s
``[B, H, P] -> [B, 1, H*P]``) is refused at trace time.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_mesh_like(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))
