"""Pallas TPU kernel: quantile binning (bucketize against per-feature splits).

bin(i, f) = #{t : values[i, f] >= thresholds[f, t]} -- a broadcast compare +
reduction over the (small) threshold axis, tiled over (instances x features)
so each VMEM tile streams HBM once.  Thresholds are padded with +inf.

Values and bins are carried instance-minor, ``(n_f, n_i)``, tiled
``(block_f, block_i)``: the TPU lowering takes a block only when its last
two dims are multiples of (8, 128) or equal the array's own dims, which a
``(block_i, block_f)`` tile of ``(n_i, n_f)`` breaks once ``n_f > block_f``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import default_interpret, round_up

BLOCK_I = 512
BLOCK_F = 32


def _bucketize_kernel(vals_ref, thr_ref, out_ref):
    v = vals_ref[...]                        # (BF, BI)
    t = thr_ref[...]                         # (BF, T)
    ge = v[:, :, None] >= t[:, None, :]
    out_ref[...] = ge.sum(axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "block_i", "block_f"))
def bucketize_pallas(values: jnp.ndarray, thresholds: jnp.ndarray,
                     interpret: bool | None = None,
                     block_i: int = BLOCK_I,
                     block_f: int = BLOCK_F) -> jnp.ndarray:
    if interpret is None:
        interpret = default_interpret()
    n_i, n_f = values.shape
    n_t = thresholds.shape[-1]
    pi, pf = round_up(max(n_i, 1), block_i), round_up(max(n_f, 1), block_f)
    vals_t = jnp.zeros((pf, pi), jnp.float32).at[:n_f, :n_i].set(values.T)
    thr_p = jnp.full((pf, n_t), jnp.inf, jnp.float32).at[:n_f].set(thresholds)

    out = pl.pallas_call(
        _bucketize_kernel,
        grid=(pi // block_i, pf // block_f),
        in_specs=[
            pl.BlockSpec((block_f, block_i), lambda i, f: (f, i)),
            pl.BlockSpec((block_f, n_t), lambda i, f: (f, 0)),
        ],
        out_specs=pl.BlockSpec((block_f, block_i), lambda i, f: (f, i)),
        out_shape=jax.ShapeDtypeStruct((pf, pi), jnp.int32),
        interpret=interpret,
    )(vals_t, thr_p)
    return out[:n_f, :n_i].T
