"""Jit'd public wrappers around the ciphertext histogram kernel.

Single-device dispatchers plus the mesh-sharded layer dispatch
(:func:`sharded_layer_ciphertext_histogram`, DESIGN.md §5/§7): instance
tiles shard over the "data" mesh axis, node blocks over "model", and the
cross-shard reduction is a *lazy-limb* int32 psum — carries stay deferred
across the collective, so one ``cipher.reduce`` after the psum yields a
result bit-identical to the single-device path (int32 addition is exact and
order-free; the in-tile fp32 dots are exact per §3 regardless of how
instances are tiled across shards).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..common import cdiv, default_interpret, round_up
from .histogram import forest_hist_pallas, hist_pallas, layer_hist_pallas
from .ref import forest_hist_ref, hist_ref, layer_hist_ref


def ciphertext_histogram(bins, cts, n_bins: int, use_pallas: bool = True,
                         interpret: bool | None = None) -> jnp.ndarray:
    """(n_i, n_f) bins x (n_i, L) limb ciphertexts -> (n_f, n_b, L) lazy sums.

    Lazy output: limb values are raw int32 sums; callers must carry-fix /
    modular-reduce (cipher.reduce) before decrypting.  Masked instances are
    marked with a negative bin index.
    """
    bins = jnp.asarray(bins, jnp.int32)
    cts = jnp.asarray(cts, jnp.int32)
    if use_pallas:
        return hist_pallas(bins, cts, n_bins, interpret=interpret)
    return hist_ref(bins, cts, n_bins)


def count_histogram(bins, n_bins: int) -> jnp.ndarray:
    """Plaintext per-bin instance counts: (n_f, n_b) int32."""
    oh = (bins[:, :, None] == jnp.arange(n_bins)[None, None, :])
    return oh.sum(axis=0).astype(jnp.int32)


def layer_ciphertext_histogram(bins, node_slot, cts, n_nodes: int,
                               n_bins: int, use_pallas: bool = True,
                               interpret: bool | None = None) -> jnp.ndarray:
    """Node-batched histogram for one tree layer: (n_i, n_f) bins x (n_i,)
    node slots x (n_i, L) limb ciphertexts -> (n_nodes, n_f, n_b, L) lazy
    sums.  One launch covers every direct-mode frontier node; masking rules
    match :func:`ciphertext_histogram` (negative bin or slot = skipped).
    """
    bins = jnp.asarray(bins, jnp.int32)
    node_slot = jnp.asarray(node_slot, jnp.int32)
    cts = jnp.asarray(cts, jnp.int32)
    if use_pallas:
        return layer_hist_pallas(bins, node_slot, cts, n_nodes, n_bins,
                                 interpret=interpret)
    return layer_hist_ref(bins, node_slot, cts, n_nodes, n_bins)


def forest_ciphertext_histogram(bins, node_slot, cts, n_nodes: int,
                                n_bins: int, use_pallas: bool = True,
                                interpret: bool | None = None) -> jnp.ndarray:
    """(tree, node)-batched histogram for one round-forest layer:
    (n_i, n_f) bins x (n_i, k) member-local node slots x (n_i, L) limb
    ciphertexts -> (k, n_nodes, n_f, n_b, L) lazy sums.  One launch covers
    every direct-mode frontier node of every member tree; masking rules
    match :func:`layer_ciphertext_histogram` per member column.
    """
    bins = jnp.asarray(bins, jnp.int32)
    node_slot = jnp.asarray(node_slot, jnp.int32)
    cts = jnp.asarray(cts, jnp.int32)
    if use_pallas:
        return forest_hist_pallas(bins, node_slot, cts, n_nodes, n_bins,
                                  interpret=interpret)
    return forest_hist_ref(bins, node_slot, cts, n_nodes, n_bins)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "mesh",
                                             "use_pallas", "interpret"))
def _sharded_layer_hist(bins, node_slot, cts, n_nodes: int, n_bins: int,
                        mesh, use_pallas: bool, interpret: bool):
    sizes = dict(mesh.shape)
    dd, mm = sizes.get("data", 1), sizes.get("model", 1)
    n_i, n_f = bins.shape
    L = cts.shape[-1]
    npm = cdiv(n_nodes, mm)              # node block per model shard
    pi = round_up(max(n_i, 1), dd)
    # pad rows land on the last data shard with node_slot = -1 (ignored)
    bins_p = jnp.full((pi, n_f), -1, jnp.int32).at[:n_i].set(bins)
    slot_p = jnp.full((pi,), -1, jnp.int32).at[:n_i].set(node_slot)
    cts_p = jnp.zeros((pi, L), jnp.int32).at[:n_i].set(cts)

    def local(b, s, c):
        m_idx = jax.lax.axis_index("model")
        ls = s - m_idx * npm             # slot within this model shard's block
        ls = jnp.where((ls >= 0) & (ls < npm), ls, -1)
        if use_pallas:
            h = layer_hist_pallas(b, ls, c, npm, n_bins, interpret=interpret)
        else:
            h = layer_hist_ref(b, ls, c, npm, n_bins)
        # lazy-limb all-reduce: int32 sums, carries still deferred (§3);
        # then gather the node blocks over "model" -- the split-finding path
        # consumes every node (layer cumsum + shuffled split_infos concat),
        # so this collective is inherent to the protocol.
        h = jax.lax.psum(h, "data")
        return jax.lax.all_gather(h, "model", axis=0, tiled=True)

    # check_vma=False: the per-shard Pallas call declares a plain output
    # shape, and all_gather's replicated result is typed as varying
    out = jax.shard_map(local, mesh=mesh,
                        in_specs=(P("data", None), P("data"), P("data", None)),
                        out_specs=P(None, None, None, None),
                        check_vma=False)(bins_p, slot_p, cts_p)
    return out[:n_nodes]


def sharded_layer_ciphertext_histogram(bins, node_slot, cts, n_nodes: int,
                                       n_bins: int, mesh,
                                       use_pallas: bool = True,
                                       interpret: bool | None = None
                                       ) -> jnp.ndarray:
    """Mesh-sharded :func:`layer_ciphertext_histogram`.

    Each (data, model) shard runs the layer kernel on its local instance
    tile for its node block only, then the lazy int32 limb sums psum over
    "data" and the node blocks all-gather over "model".  Bit-identical to
    the single-device dispatch for any mesh factorization.  Returns the
    (n_nodes, n_f, n_bins, L) global array.
    """
    if interpret is None:
        interpret = default_interpret()
    bins = jnp.asarray(bins, jnp.int32)
    node_slot = jnp.asarray(node_slot, jnp.int32)
    cts = jnp.asarray(cts, jnp.int32)
    out = _sharded_layer_hist(bins, node_slot, cts, n_nodes, n_bins, mesh,
                              use_pallas, interpret)
    # Land the gathered result on the mesh's first device: downstream
    # protocol steps (reduce / cumsum / shuffle) are small relative to
    # accumulation and would otherwise run on every replica, and they mix
    # the result with single-device arrays (cached parent histograms).
    return jax.device_put(out, mesh.devices.flat[0])


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "mesh",
                                             "use_pallas", "interpret"))
def _sharded_forest_hist(bins, node_slot, cts, n_nodes: int, n_bins: int,
                         mesh, use_pallas: bool, interpret: bool):
    sizes = dict(mesh.shape)
    dd, mm = sizes.get("data", 1), sizes.get("model", 1)
    n_i, n_f = bins.shape
    k = node_slot.shape[1]
    L = cts.shape[-1]
    npm = cdiv(n_nodes, mm)              # member-local node block per shard
    pi = round_up(max(n_i, 1), dd)
    bins_p = jnp.full((pi, n_f), -1, jnp.int32).at[:n_i].set(bins)
    slot_p = jnp.full((pi, k), -1, jnp.int32).at[:n_i].set(node_slot)
    cts_p = jnp.zeros((pi, L), jnp.int32).at[:n_i].set(cts)

    def local(b, s, c):
        m_idx = jax.lax.axis_index("model")
        ls = s - m_idx * npm             # member-local slot within this block
        ls = jnp.where((ls >= 0) & (ls < npm), ls, -1)
        if use_pallas:
            h = forest_hist_pallas(b, ls, c, npm, n_bins, interpret=interpret)
        else:
            h = forest_hist_ref(b, ls, c, npm, n_bins)
        h = jax.lax.psum(h, "data")
        # gather the member-local node blocks over "model" (axis 1 of the
        # (k, npm, n_f, n_b, L) local result)
        return jax.lax.all_gather(h, "model", axis=1, tiled=True)

    # check_vma=False: as in the layer variant
    out = jax.shard_map(local, mesh=mesh,
                        in_specs=(P("data", None), P("data", None),
                                  P("data", None)),
                        out_specs=P(None, None, None, None, None),
                        check_vma=False)(bins_p, slot_p, cts_p)
    return out[:, :n_nodes]


def sharded_forest_ciphertext_histogram(bins, node_slot, cts, n_nodes: int,
                                        n_bins: int, mesh,
                                        use_pallas: bool = True,
                                        interpret: bool | None = None
                                        ) -> jnp.ndarray:
    """Mesh-sharded :func:`forest_ciphertext_histogram`: the forest kernel's
    member axis rides along unchanged while instance tiles shard over "data"
    and member-local node blocks over "model".  Bit-identical to the
    single-device dispatch.  Returns the (k, n_nodes, n_f, n_bins, L) global
    array landed on the mesh's first device, like the layer variant."""
    if interpret is None:
        interpret = default_interpret()
    bins = jnp.asarray(bins, jnp.int32)
    node_slot = jnp.asarray(node_slot, jnp.int32)
    cts = jnp.asarray(cts, jnp.int32)
    out = _sharded_forest_hist(bins, node_slot, cts, n_nodes, n_bins, mesh,
                               use_pallas, interpret)
    return jax.device_put(out, mesh.devices.flat[0])


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins"))
def _layer_hist_segsum(bins, node_slot, cts, n_nodes: int, n_bins: int):
    """Scatter-add accumulation of one row block: (n_nodes, n_f, n_b, L)
    lazy int32 sums via a feature-vmapped segment_sum.  Bit-identical to the
    kernel paths (int32 limb addition is exact and order-free) but with
    O(block · L) temporaries instead of the reference einsum's
    O(block · n_f · n_nodes · n_b) one-hot — the accumulation the streamed
    dispatch uses where Pallas would run in interpret mode."""
    nseg = n_nodes * n_bins

    def one_feature(bcol):
        ok = (node_slot >= 0) & (bcol >= 0)
        idx = jnp.where(ok, node_slot * n_bins + bcol, nseg)
        return jax.ops.segment_sum(cts, idx, num_segments=nseg + 1)[:nseg]

    h = jax.vmap(one_feature, in_axes=1)(bins)      # (n_f, nseg, L)
    return h.reshape(h.shape[0], n_nodes, n_bins,
                     h.shape[-1]).transpose(1, 0, 2, 3)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins"))
def _forest_hist_segsum(bins, node_slot, cts, n_nodes: int, n_bins: int):
    """Member-batched :func:`_layer_hist_segsum`: node_slot is (n_i, k);
    returns (k, n_nodes, n_f, n_b, L) lazy sums."""
    return jax.vmap(
        lambda scol: _layer_hist_segsum(bins, scol, cts, n_nodes, n_bins),
        in_axes=1)(node_slot)


def streamed_layer_ciphertext_histogram(blocks, n_nodes: int, n_bins: int,
                                        forest: int = 0, mesh=None,
                                        use_pallas: bool = True,
                                        interpret: bool | None = None,
                                        on_block=None) -> jnp.ndarray:
    """Out-of-core layer accumulation (DESIGN.md §13): iterate
    ``(bins_blk, node_slot_blk, cts_blk)`` row blocks and sum their lazy
    int32 partial histograms; the caller runs ONE ``cipher.reduce`` on the
    result, exactly as for the monolithic dispatch.

    Bit-identity is the §3 psum-then-carry algebra applied over *time*
    instead of over devices: int32 limb addition is exact and order-free,
    so per-block partial sums + one deferred carry-fix equal the monolithic
    launch wherever the monolithic launch is itself exact (the cross-block
    accumulator has the same ~2^31 per-(node, feature, bin, limb) headroom
    as the kernel's own cross-tile accumulator).  Peak device memory is
    O(block + nodes) per launch, not O(rows).

    Per block the accumulation runs the mesh-sharded dispatch (when a
    multi-device mesh is given), the Pallas kernel (compiled backends), or
    the segment-sum path (CPU, where Pallas would interpret).  ``on_block``
    is an accounting hook receiving the device bytes uploaded per launch.
    """
    if interpret is None:
        interpret = default_interpret()
    multi = mesh is not None and mesh.devices.size > 1
    acc = None
    for bins_blk, slot_blk, cts_blk in blocks:
        bins_blk = jnp.asarray(bins_blk, jnp.int32)
        slot_blk = jnp.asarray(slot_blk, jnp.int32)
        cts_blk = jnp.asarray(cts_blk, jnp.int32)
        if on_block is not None:
            on_block(bins_blk.nbytes + slot_blk.nbytes + cts_blk.nbytes)
        if multi:
            if forest:
                h = sharded_forest_ciphertext_histogram(
                    bins_blk, slot_blk, cts_blk, n_nodes, n_bins, mesh,
                    use_pallas=use_pallas, interpret=interpret)
            else:
                h = sharded_layer_ciphertext_histogram(
                    bins_blk, slot_blk, cts_blk, n_nodes, n_bins, mesh,
                    use_pallas=use_pallas, interpret=interpret)
        elif use_pallas and not interpret:
            if forest:
                h = forest_hist_pallas(bins_blk, slot_blk, cts_blk, n_nodes,
                                       n_bins, interpret=interpret)
            else:
                h = layer_hist_pallas(bins_blk, slot_blk, cts_blk, n_nodes,
                                      n_bins, interpret=interpret)
        else:
            if forest:
                h = _forest_hist_segsum(bins_blk, slot_blk, cts_blk, n_nodes,
                                        n_bins)
            else:
                h = _layer_hist_segsum(bins_blk, slot_blk, cts_blk, n_nodes,
                                       n_bins)
        acc = h if acc is None else acc + h
    return acc


def psum_wire_bytes(mesh, shard_bytes: int) -> int:
    """Analytic intra-party collective cost of the layer psum: a ring
    all-reduce over the ``data`` axis moves 2·(d-1)/d · S bytes per device
    for a per-shard payload of S bytes; there is one independent ring per
    ``model`` coordinate, so the mesh-wide total is m · 2·(d-1)·S."""
    sizes = dict(mesh.shape)
    d = sizes.get("data", 1)
    m = sizes.get("model", 1)
    return m * 2 * (d - 1) * int(shard_bytes)


def allgather_wire_bytes(mesh, global_bytes: int) -> int:
    """Analytic cost of replicating the node-sharded layer histogram over
    "model": each device receives (m-1)/m of the global array, summed over
    all devices in the mesh."""
    sizes = dict(mesh.shape)
    m = sizes.get("model", 1)
    n_dev = int(np.prod(list(sizes.values())))
    return (m - 1) * n_dev * (int(global_bytes) // max(m, 1))


def layer_count_histogram(bins, node_slot, n_nodes: int, n_bins: int):
    """Plaintext per-(node, feature, bin) instance counts:
    (n_nodes, n_f, n_b) int32.  Counts never touch the cipher domain, so
    this is a flat numpy bincount over the (feature, node, bin) composite
    index -- O(n_i * n_f) memory, no one-hot materialized."""
    bins = np.asarray(bins, np.int64)
    node_slot = np.asarray(node_slot, np.int64)
    n_f = bins.shape[1]
    comp = node_slot[:, None] * n_bins + bins       # (n_i, n_f)
    valid = (node_slot[:, None] >= 0) & (bins >= 0)
    f_idx = np.broadcast_to(np.arange(n_f)[None, :], comp.shape)
    flat = (f_idx * (n_nodes * n_bins) + comp)[valid]
    out = np.bincount(flat, minlength=n_f * n_nodes * n_bins)
    return out.astype(np.int32).reshape(n_f, n_nodes,
                                        n_bins).transpose(1, 0, 2)
