"""Shared test config.

The suite jit-compiles hundreds of distinct GBDT programs per worker
(kernels, ciphers and key widths, mesh layouts, objectives); on a small
host the accumulated XLA executables can exhaust memory late in the run.
Clearing JAX caches between modules bounds the footprint without touching
test semantics.
"""

import jax
import pytest


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    yield
    jax.clear_caches()
