"""Persistent XLA compilation cache at a fixed, checkout-relative path."""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """``<checkout>/.repro_cache/jax`` (src/repro/compile_cache.py -> up 3)."""
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(here))
    return os.path.join(root, ".repro_cache", "jax")


def enable() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When ``$JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    it is left alone. Otherwise the cache goes to `default_dir`: a fixed
    path, so a later process in the same checkout finds what this one
    compiled. Call it from a program's entry point, never at import.
    """
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax

    path = default_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
