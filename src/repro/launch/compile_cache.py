"""JAX's persistent compilation cache, placed by the entry points.

Call ``use_compile_cache()`` first thing in a ``main`` (never on import).
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here; otherwise the cache is ``<repo>/.jax_cache`` (git-ignored).  The
path is part of the cache's key, so it is fixed: never a temp name.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
