"""Pallas TPU kernel: ciphertext histogram accumulation as one-hot matmul.

The hot loop of SecureBoost+ (Algorithm 1/5) is ``H[f][bid] += [[gh_i]]``: a
scatter-add of big integers into (feature, bin) cells.  On TPU we lower the
scatter as a *matmul* so it runs on the MXU:

    hist[f*n_b + b, l] = sum_i onehot(bins[i, f] == b) * cts[i, l]

per (feature-block x instance-block) tile.  Limbs are radix-2**8 so the
within-tile fp32 dot is exact (sums < 2**24 for tiles <= 2**15 rows larger
than any VMEM tile we use), and cross-tile accumulation happens in int32 in
the output block (lazy carry: the caller carry-fixes / Barrett-reduces once
per bin, not once per add -- see DESIGN.md §3).

Grid: (feature_blocks, instance_blocks); instance axis is the innermost
reduction axis, revisiting the same output block.

Layout: the TPU lowering takes a block only when its last two dims are
multiples of (8, 128) or equal the array's own dims.  A ``(block_i,
block_f)`` tile of an ``(n_i, n_f)`` bins matrix breaks that rule once
``n_f > block_f``, so the wrappers carry bins instance-minor, ``(n_f,
n_i)``, tiled ``(block_f, block_i)``, and node slots as ``(1, n_i)`` (forest
member slots as ``(k, 1, n_i)``) tiled ``(1, block_i)``.  That holds at any
feature count and keeps the minor dim dense in HBM (an ``(n_i, 8)`` array
pads its last dim to 128 lanes there).  Each tile's one-hot is then built
column-major, ``(BF*C, BI)``, and contracts the instance axis as a plain
``(BF*C, BI) @ (BI, L)`` MXU matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import default_interpret, round_up

# VMEM budget at defaults (fp32): onehot (8*32)x256=256KB, cts 256xLx4,
# out 8x32xLx4 -- comfortably < 16MB for L <= 512.
BLOCK_I = 256
BLOCK_F = 8
# layer-batched variant: onehot grows to (BF*BN*n_b)x256; at the defaults
# (BF=8, BN=8, n_b=32) that is 2MB fp32, out block 8x8x32xLx4.
BLOCK_N = 8


def _onehot_accumulate(comp, cts_ref, out_ref, n_cols: int):
    """out += onehot(comp) @ cts for one tile.

    comp: (BF, BI) int32 one-hot column per (feature, instance) in
    [0, n_cols); negative = contributes nothing.  out_ref: a block whose
    size is BF * n_cols * L."""
    bf, bi = comp.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (bf, n_cols, bi), 1)
    oh = (comp[:, None, :] == cols).astype(jnp.float32)
    oh = oh.reshape(bf * n_cols, bi)
    cts = cts_ref[...].astype(jnp.float32)     # (BI, L)
    part = jnp.dot(oh, cts, preferred_element_type=jnp.float32)
    out_ref[...] += part.astype(jnp.int32).reshape(out_ref.shape)


def _hist_kernel(bins_ref, cts_ref, out_ref, *, n_bins: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    _onehot_accumulate(bins_ref[...], cts_ref, out_ref, n_bins)


def _pad_bins_t(bins, pi: int, pf: int):
    """(n_i, n_f) bins -> (pf, pi) instance-minor, padding masked (-1)."""
    n_i, n_f = bins.shape
    return jnp.full((pf, pi), -1, jnp.int32).at[:n_f, :n_i].set(bins.T)


@functools.partial(jax.jit, static_argnames=("n_bins", "interpret",
                                             "block_i", "block_f"))
def hist_pallas(bins: jnp.ndarray, cts: jnp.ndarray, n_bins: int,
                interpret: bool | None = None,
                block_i: int = BLOCK_I, block_f: int = BLOCK_F) -> jnp.ndarray:
    """Ciphertext histogram: see ref.hist_ref for semantics.

    bins: (n_i, n_f) int32 (negative = masked), cts: (n_i, L) int32.
    Returns (n_f, n_bins, L) int32 lazy limb sums.
    """
    if interpret is None:
        interpret = default_interpret()
    n_i, n_f = bins.shape
    L = cts.shape[-1]
    pi, pf = round_up(max(n_i, 1), block_i), round_up(max(n_f, 1), block_f)
    bins_t = _pad_bins_t(bins, pi, pf)
    cts_p = jnp.zeros((pi, L), jnp.int32).at[:n_i].set(cts)

    grid = (pf // block_f, pi // block_i)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, n_bins=n_bins),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_f, block_i), lambda f, i: (f, i)),
            pl.BlockSpec((block_i, L), lambda f, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_f, n_bins, L), lambda f, i: (f, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((pf, n_bins, L), jnp.int32),
        interpret=interpret,
    )(bins_t, cts_p)
    return out[:n_f]


def _layer_hist_kernel(bins_ref, node_ref, cts_ref, out_ref, *, n_bins: int,
                       block_n: int, n_axis: int, i_axis: int):
    # shared by the layer grid (node, feature, instance) and the forest grid
    # (member, node, feature, instance): the member axis only selects which
    # slot row the BlockSpec hands in, so the body is identical
    n_blk = pl.program_id(n_axis)

    @pl.when(pl.program_id(i_axis) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bins = bins_ref[...]                       # (BF, BI) int32
    local = node_ref[...] - n_blk * block_n    # (1, BI) slot within block
    ok = (local >= 0) & (local < block_n) & (bins >= 0)
    comp = jnp.where(ok, local * n_bins + bins, -1)
    _onehot_accumulate(comp, cts_ref, out_ref, block_n * n_bins)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "interpret",
                                             "block_i", "block_f", "block_n"))
def layer_hist_pallas(bins: jnp.ndarray, node_slot: jnp.ndarray,
                      cts: jnp.ndarray, n_nodes: int, n_bins: int,
                      interpret: bool | None = None,
                      block_i: int = BLOCK_I, block_f: int = BLOCK_F,
                      block_n: int = BLOCK_N) -> jnp.ndarray:
    """Layer-batched ciphertext histogram: see ref.layer_hist_ref.

    One launch accumulates every direct-mode frontier node of a tree layer:
    the one-hot axis is the composite ``node_slot[i] * n_bins + bins[i, f]``,
    tiled over (node_block, feature_block, instance_block) with the instance
    axis innermost (revisiting the same output block).

    bins: (n_i, n_f) int32 (negative = masked), node_slot: (n_i,) int32
    (negative = row not in any direct node), cts: (n_i, L) int32.
    Returns (n_nodes, n_f, n_bins, L) int32 lazy limb sums.
    """
    if interpret is None:
        interpret = default_interpret()
    n_i, n_f = bins.shape
    L = cts.shape[-1]
    block_n = min(block_n, round_up(max(n_nodes, 1), 2))
    pi = round_up(max(n_i, 1), block_i)
    pf = round_up(max(n_f, 1), block_f)
    pn = round_up(max(n_nodes, 1), block_n)
    bins_t = _pad_bins_t(bins, pi, pf)
    node_p = jnp.full((1, pi), -1, jnp.int32).at[0, :n_i].set(node_slot)
    cts_p = jnp.zeros((pi, L), jnp.int32).at[:n_i].set(cts)

    grid = (pn // block_n, pf // block_f, pi // block_i)
    out = pl.pallas_call(
        functools.partial(_layer_hist_kernel, n_bins=n_bins, block_n=block_n,
                          n_axis=0, i_axis=2),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_f, block_i), lambda n, f, i: (f, i)),
            pl.BlockSpec((1, block_i), lambda n, f, i: (0, i)),
            pl.BlockSpec((block_i, L), lambda n, f, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_f, block_n, n_bins, L),
                               lambda n, f, i: (f, n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((pf, pn, n_bins, L), jnp.int32),
        interpret=interpret,
    )(bins_t, node_p, cts_p)
    return out[:n_f, :n_nodes].transpose(1, 0, 2, 3)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "interpret",
                                             "block_i", "block_f", "block_n"))
def forest_hist_pallas(bins: jnp.ndarray, node_slot: jnp.ndarray,
                       cts: jnp.ndarray, n_nodes: int, n_bins: int,
                       interpret: bool | None = None,
                       block_i: int = BLOCK_I, block_f: int = BLOCK_F,
                       block_n: int = BLOCK_N) -> jnp.ndarray:
    """(tree, node)-batched ciphertext histogram: see ref.forest_hist_ref.

    One launch accumulates every direct-mode frontier node of every member
    tree of a round-forest layer.  The grid gains a leading member axis; the
    slot BlockSpec carves out member t's slot row of the (k, 1, n_i) slot
    array, and each (t, f, n) output block is visited contiguously over the
    innermost instance axis.

    bins: (n_i, n_f) int32 (negative = masked), node_slot: (n_i, k) int32
    member-local slots (negative = row not in any direct node of that
    member), cts: (n_i, L) int32.
    Returns (k, n_nodes, n_f, n_bins, L) int32 lazy limb sums.
    """
    if interpret is None:
        interpret = default_interpret()
    n_i, n_f = bins.shape
    k = node_slot.shape[1]
    L = cts.shape[-1]
    block_n = min(block_n, round_up(max(n_nodes, 1), 2))
    pi = round_up(max(n_i, 1), block_i)
    pf = round_up(max(n_f, 1), block_f)
    pn = round_up(max(n_nodes, 1), block_n)
    bins_t = _pad_bins_t(bins, pi, pf)
    slot_p = jnp.full((k, 1, pi), -1, jnp.int32).at[:, 0, :n_i].set(
        node_slot.T)
    cts_p = jnp.zeros((pi, L), jnp.int32).at[:n_i].set(cts)

    grid = (k, pn // block_n, pf // block_f, pi // block_i)
    out = pl.pallas_call(
        functools.partial(_layer_hist_kernel, n_bins=n_bins, block_n=block_n,
                          n_axis=1, i_axis=3),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_f, block_i), lambda t, n, f, i: (f, i)),
            pl.BlockSpec((None, 1, block_i), lambda t, n, f, i: (t, 0, i)),
            pl.BlockSpec((block_i, L), lambda t, n, f, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_f, block_n, n_bins, L),
                               lambda t, n, f, i: (t, f, n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, pf, pn, n_bins, L), jnp.int32),
        interpret=interpret,
    )(bins_t, slot_p, cts_p)
    return out[:, :n_f, :n_nodes].transpose(0, 2, 1, 3, 4)
