"""Placement of JAX's persistent compilation cache.

The cache directory is part of the cache key, so it must not move between
runs: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing else is set; otherwise the cache lives at one fixed path inside the
checkout (``<repo>/.jax_cache``, git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory.  Call before the first compilation."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
