"""The entry points' persistent compile cache: a fixed, git-ignored path
inside the checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
from pathlib import Path

import jax

from repro.launch.cache import setup_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_cache_in_checkout_when_env_unset(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        setup_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_cache_left_to_jax_when_env_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    setup_compile_cache()
    assert jax.config.jax_compilation_cache_dir == was
