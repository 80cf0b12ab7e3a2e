"""Compile-only checks of the Pallas kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip would refuse
(block shapes off the (8, 128) tiling, VMEM overruns), which interpret mode
never sees.  Nothing runs.  Widths are the main path's: 65,536 rows,
1024-bit keys (131-limb histogram accumulators), 32 bins, a 14-feature
party (HIGGS split in two) and a 1000-feature one (epsilon split in two).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.binning.binning import bucketize_pallas
from repro.kernels.histogram.histogram import (forest_hist_pallas,
                                               layer_hist_pallas)
from repro.kernels.modmul.modmul import mul_fixed_pallas

ROWS = 65_536
LIMBS = 128                 # 1024-bit modulus, radix 2**8
HIST_WIDTH = LIMBS + 3      # lazy-accumulation headroom (DESIGN.md §3)
N_BINS = 32
N_NODES = 16                # a depth-5 tree's widest direct layer
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:              # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert CUSTOM_CALL in compiled.as_text()
    return compiled


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n_f", [14, 1000])
def test_layer_hist_compiles(one_chip, n_f):
    _compile(lambda b, s, c: layer_hist_pallas(b, s, c, N_NODES, N_BINS,
                                               interpret=False),
             _spec(one_chip, (ROWS, n_f)), _spec(one_chip, (ROWS,)),
             _spec(one_chip, (ROWS, HIST_WIDTH)))


def test_forest_hist_compiles(one_chip):
    _compile(lambda b, s, c: forest_hist_pallas(b, s, c, N_NODES, N_BINS,
                                                interpret=False),
             _spec(one_chip, (ROWS, 14)), _spec(one_chip, (ROWS, 4)),
             _spec(one_chip, (ROWS, HIST_WIDTH)))


def test_mul_fixed_compiles(one_chip):
    _compile(lambda x, t: mul_fixed_pallas(x, t, interpret=False),
             _spec(one_chip, (ROWS, LIMBS)),
             _spec(one_chip, (LIMBS, 2 * LIMBS + 2)))


@pytest.mark.parametrize("n_f", [14, 1000])
def test_bucketize_compiles(one_chip, n_f):
    _compile(lambda v, t: bucketize_pallas(v, t, interpret=False),
             _spec(one_chip, (ROWS, n_f), jnp.float32),
             _spec(one_chip, (n_f, N_BINS - 1), jnp.float32))
