"""Persistent XLA compilation cache for the entry points (serve launcher,
benchmark orchestrator, chip smoke test).

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and this
leaves the choice alone. Otherwise the cache lives at ``<checkout>/.jax_cache``
— a fixed path: the directory is part of the cache key, so a path that moves
(a pid, a temp name, a time) would never hit.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"
ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
