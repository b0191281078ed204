"""JAX's persistent compilation cache, kept at one fixed place.

The path is part of every cache key, so a directory that moves between runs
never hits. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing is changed here; otherwise the cache lives at ``<repo>/.jax_cache``
inside the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
