"""Placement of the persistent compilation cache (``repro.launch.cache``).

Each case runs in a fresh interpreter: the cache directory is process-wide
JAX state, read once at the first compilation.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch.cache import ENV_VAR, REPO_CACHE_DIR

REPO = Path(__file__).resolve().parents[1]

CHILD = """
import jax, jax.numpy as jnp
from repro.launch.cache import use_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


def _child(env_dir, compile_: bool):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.pop(ENV_VAR, None)
    if env_dir is not None:
        env[ENV_VAR] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", CHILD.format(compile=compile_)],
                         env=env, capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    return out[-2:]


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_placement(tmp_path, env_set):
    """With ``JAX_COMPILATION_CACHE_DIR`` set the cache is that directory
    and is written there; unset, it is the one fixed in-checkout path
    (never a temp, pid or time-based one), the same in every process."""
    if env_set:
        cache = tmp_path / "jax-cache"
        used, configured = _child(cache, compile_=True)
        assert used == configured == str(cache)
        assert any(cache.iterdir()), "nothing was cached"
    else:
        first = _child(None, compile_=False)
        assert first == _child(None, compile_=False)
        assert first == [str(REPO_CACHE_DIR)] * 2
        assert REPO_CACHE_DIR == REPO / ".jax_cache"
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
