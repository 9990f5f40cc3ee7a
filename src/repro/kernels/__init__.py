"""Pallas TPU kernels for the substrate's compute hot spots.

The paper (OODIDA active-code replacement) has no kernel-level
contribution; these kernels serve the model substrate's hot spots
(attention, SSD scan, RMSNorm, grouped expert matmul). Layout per the
deliverable spec: ``<name>.py`` holds the ``pl.pallas_call`` + BlockSpec
kernel, ``ops.py`` the jit'd dispatch wrappers, ``ref.py`` the pure-jnp
oracles.
"""
from repro.kernels import ops, ref, xla

__all__ = ["ops", "ref", "xla"]
