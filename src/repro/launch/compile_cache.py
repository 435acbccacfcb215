"""JAX's persistent compilation cache, at a place that can be set from
outside.

The cache directory is part of what a later run must find again, so it is
either the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable
itself; nothing here overrides it) or the fixed ``<checkout>/.jax_cache``
(git-ignored) — never a temp, pid or time-based path, which would never
hit.  Entry points call :func:`enable_compile_cache` before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path


__all__ = ["enable_compile_cache", "DEFAULT_DIR", "ENV"]
