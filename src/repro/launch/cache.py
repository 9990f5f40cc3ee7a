"""The persistent XLA compile cache every entry point shares.

``chip_smoke.py``, the train and serve launchers and
``benchmarks/run.py`` call ``setup_compile_cache()`` before their first
compile. A process that compiled a program once then finds it again in
a later run on the same backend, instead of paying the compile anew.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (git-ignored). A fixed path: a cache directory
# named after a temp dir, a pid or the time would never be found again.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> None:
    """Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
    and no directory is set here; otherwise the cache is ``CACHE_DIR``."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
