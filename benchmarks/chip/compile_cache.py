"""Placement of JAX's persistent compilation cache for benchmark runs.

The cache directory is part of the cache key, so it never moves: it is
``<checkout>/.jax_cache`` (git-ignored), fixed in this file and set over
whatever the environment names, so that two checkouts measured side by
side share no compiled program.  Every program is cached, however fast it
compiled, and nothing is evicted (a size limit from the environment would
turn on JAX's eviction, which failed on missing access-time files), so
that only a checkout's first run of a cell compiles.
"""

from __future__ import annotations

from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def place() -> str:
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(CACHE_DIR)
