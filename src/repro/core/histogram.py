"""Histogram engines (paper Algorithms 1 & 5), node-level and layer-batched.

Two engines share one split-finding path:

* :class:`CipherHistogram` -- host side.  Accumulates packed-GH ciphertexts
  into (feature, bin) cells via the Pallas one-hot-matmul kernel (lazy limb
  sums), then canonicalizes once per bin (``cipher.reduce``: carry-fix +
  Barrett).  Supports ciphertext histogram subtraction (§4.3), the sparse
  zero-bin recovery trick (§6.2), and bin cumsum in the ciphertext domain.
  Ciphertext batches carry a slot axis (SBT-MO packs ``n_k`` ciphertexts per
  instance): per-instance cts are (n, n_slots, L) limbs (or (n, n_slots)
  object ints for the Paillier oracle); histograms are (n_f, n_b, n_slots, L)
  (resp. (n_f, n_b, n_slots)).  Binary tasks use n_slots = 1.

* :class:`PlainHistogram` -- guest side (and the local-XGBoost baseline).
  Same shapes in plaintext float64 via ``np.add.at``.

Both engines additionally expose :meth:`layer_histograms`, the layer-batched
hot path (see DESIGN.md §6): every direct-mode frontier node of one tree
layer is accumulated by a SINGLE kernel launch over the composite one-hot
``node_slot * n_bins + bin``, histogram subtraction for the remaining nodes
is applied in the still-lazy limb domain (``cipher.lazy_sub``), and ONE
``cipher.reduce`` canonicalizes the whole layer.  This collapses
O(2**depth) kernel launches and Barrett passes per layer to O(1).

The cipher layer path operates on a ``core.frontier.CipherFrontier`` — the
device-resident layer state (DESIGN.md §7): bins masked and ciphertexts
width-padded once per tree, parent histograms cached as device arrays.
When the engine is built with a (data, model) mesh the single dispatch is
``shard_map``-sharded (per-shard kernel + lazy int32 psum over "data",
node blocks over "model") and remains bit-identical to one device.
"""

from __future__ import annotations

import numpy as np

from ..kernels.histogram import (allgather_wire_bytes, ciphertext_histogram,
                                 count_histogram, forest_ciphertext_histogram,
                                 layer_ciphertext_histogram,
                                 layer_count_histogram, psum_wire_bytes,
                                 sharded_forest_ciphertext_histogram,
                                 sharded_layer_ciphertext_histogram,
                                 streamed_layer_ciphertext_histogram)
from .binning import BinnedData

# Round-forest global node ids: gid = member * GID_STRIDE + member-local nid.
# Host-side dicts (histogram cache, shuffle perms, split tables) key on the
# opaque gid; k = 1 degenerates to gid == nid, i.e. the classic layer path.
GID_STRIDE = 1 << 20


class PlainHistogram:
    """Plaintext (g, h, count) histograms: (n_f, n_b) float64 / int64.

    ``row_block > 0`` makes the layer path iterate the concatenated row
    index in contiguous chunks (out-of-core guest, DESIGN.md §13): the
    O(rows) gather temporaries (bins / g / h / composite-index slices)
    shrink to O(block) while every ``np.add.at`` still applies the same
    additions to each cell in the same order — float64 accumulation is
    sequential either way, so the result is bit-identical."""

    def __init__(self, n_bins: int, sparse: bool = False, row_block: int = 0):
        self.n_bins = n_bins
        self.sparse = sparse
        self.row_block = row_block

    def node_histogram(self, data: BinnedData, g: np.ndarray, h: np.ndarray,
                       rows: np.ndarray):
        bins = data.bins[rows]                        # (r, n_f)
        n_f = bins.shape[1]
        out_dim = np.asarray(g).shape[1:]             # () scalar or (l,) MO
        G = np.zeros((n_f, self.n_bins) + out_dim)
        H = np.zeros((n_f, self.n_bins) + out_dim)
        C = np.zeros((n_f, self.n_bins), np.int64)
        gr, hr = g[rows], h[rows]
        if self.sparse and data.zero_mask is not None:
            zmask = data.zero_mask[rows]
            for f in range(n_f):
                keep = ~zmask[:, f]
                np.add.at(G[f], bins[keep, f], gr[keep])
                np.add.at(H[f], bins[keep, f], hr[keep])
                np.add.at(C[f], bins[keep, f], 1)
                zb = int(data.zero_bins[f])
                G[f, zb] += gr.sum(axis=0) - G[f].sum(axis=0)
                H[f, zb] += hr.sum(axis=0) - H[f].sum(axis=0)
                C[f, zb] += len(rows) - C[f].sum()
        else:
            for f in range(n_f):
                np.add.at(G[f], bins[:, f], gr)
                np.add.at(H[f], bins[:, f], hr)
                np.add.at(C[f], bins[:, f], 1)
        return (G, H, C)

    def layer_histograms(self, data: BinnedData, g: np.ndarray, h: np.ndarray,
                         node_rows: dict, direct: list, subtract: list,
                         cache: dict) -> dict:
        """Batched node_histogram for one tree layer.

        node_rows: {nid: row ids}; direct: nids accumulated directly (one
        composite ``np.add.at`` pass per feature); subtract: (nid, parent,
        sibling) triples resolved as parent - sibling from ``cache`` /this
        layer's direct results.  Returns {nid: (G, H, C)}.
        """
        out = {}
        if direct:
            n_d, n_b = len(direct), self.n_bins
            rows_cat = np.concatenate([node_rows[nid] for nid in direct])
            slot_cat = np.concatenate(
                [np.full(len(node_rows[nid]), k, np.int64)
                 for k, nid in enumerate(direct)])
            n_f = data.n_features
            out_dim = np.asarray(g).shape[1:]
            G = np.zeros((n_f, n_d * n_b) + out_dim)
            H = np.zeros((n_f, n_d * n_b) + out_dim)
            C = np.zeros((n_f, n_d * n_b), np.int64)
            sparse = self.sparse and data.zero_mask is not None
            if sparse:
                gt = np.zeros((n_d,) + out_dim)
                ht = np.zeros((n_d,) + out_dim)
                ct = np.zeros(n_d, np.int64)
            R = len(rows_cat)
            step = self.row_block if self.row_block > 0 else max(R, 1)
            # contiguous chunks of the concatenated index: each np.add.at
            # sees the same per-cell addition sequence as one monolithic
            # pass, so chunking changes peak memory, not a single bit
            for s0 in range(0, R, step):
                rc = rows_cat[s0: s0 + step]
                sc = slot_cat[s0: s0 + step]
                bins = data.bins[rc]                  # (r, n_f)
                gr, hr = g[rc], h[rc]
                comp = sc[:, None] * n_b + bins       # composite (node, bin)
                zmask = data.zero_mask[rc] if sparse else None
                for f in range(n_f):
                    if sparse:
                        keep = ~zmask[:, f]
                        np.add.at(G[f], comp[keep, f], gr[keep])
                        np.add.at(H[f], comp[keep, f], hr[keep])
                        np.add.at(C[f], comp[keep, f], 1)
                    else:
                        np.add.at(G[f], comp[:, f], gr)
                        np.add.at(H[f], comp[:, f], hr)
                        np.add.at(C[f], comp[:, f], 1)
                if sparse:
                    np.add.at(gt, sc, gr)
                    np.add.at(ht, sc, hr)
                    ct += np.bincount(sc, minlength=n_d)
            Gn = np.moveaxis(G.reshape((n_f, n_d, n_b) + out_dim), 1, 0)
            Hn = np.moveaxis(H.reshape((n_f, n_d, n_b) + out_dim), 1, 0)
            Cn = np.moveaxis(C.reshape(n_f, n_d, n_b), 1, 0)
            if sparse:
                for f in range(n_f):
                    zb = int(data.zero_bins[f])
                    Gn[:, f, zb] += gt - Gn[:, f].sum(axis=1)
                    Hn[:, f, zb] += ht - Hn[:, f].sum(axis=1)
                    Cn[:, f, zb] += ct - Cn[:, f].sum(axis=1)
            for k, nid in enumerate(direct):
                out[nid] = (Gn[k], Hn[k], Cn[k])
        for nid, par, sib in subtract:
            out[nid] = self.subtract(cache[par], out[sib])
        return out

    @staticmethod
    def subtract(parent, child):
        return tuple(p - c for p, c in zip(parent, child))

    @staticmethod
    def cumsum(hist):
        return tuple(np.cumsum(x, axis=1) for x in hist)


class CipherHistogram:
    """Ciphertext histograms over limb arrays (or Paillier object arrays)."""

    def __init__(self, cipher, n_bins: int, sparse: bool = False,
                 use_pallas: bool = True, stats=None, mesh=None,
                 tracer=None):
        from ..obs.trace import NULL_TRACER
        self.cipher = cipher
        self.n_bins = n_bins
        self.sparse = sparse
        self.use_pallas = use_pallas
        self.stats = stats          # optional party.Stats for launch counts
        self.mesh = mesh            # optional (data, model) mesh (DESIGN §5)
        self.tracer = tracer if tracer is not None else NULL_TRACER

    def _mesh_devices(self) -> int:
        return self.mesh.devices.size if self.mesh is not None else 1

    def _count_launch(self):
        if self.stats is not None:
            self.stats.n_hist_launches += 1

    # -- core accumulation ------------------------------------------------
    def node_histogram(self, data: BinnedData, cts, rows: np.ndarray):
        """cts: (n, n_slots, L) limbs or (n, n_slots) object ints.
        Returns (hist, counts)."""
        bins = data.bins[rows].astype(np.int32)
        if self.sparse and data.zero_mask is not None:
            bins = np.where(data.zero_mask[rows], -1, bins)
        counts = np.asarray(count_histogram(bins, self.n_bins)).astype(np.int64)

        if self.cipher.backend == "limb":
            hist = self._limb_hist(bins, cts, rows)
        else:
            hist = self._pyobj_hist(bins, cts, rows)

        if self.sparse and data.zero_mask is not None:
            hist = self._sparse_fix(data, hist, cts, rows)
            zb = np.asarray(data.zero_bins, np.int64)
            for f in range(counts.shape[0]):
                counts[f, zb[f]] += len(rows) - counts[f].sum()
        return hist, counts

    def _limb_hist(self, bins, cts, rows):
        import jax.numpy as jnp
        sel = jnp.asarray(cts)[jnp.asarray(np.asarray(rows, np.int64))]
        n, n_slots, per = sel.shape
        width = self.cipher.hist_width
        padded = jnp.pad(sel, ((0, 0), (0, 0), (0, width - per)))
        lazy = ciphertext_histogram(bins, padded.reshape(n, n_slots * width),
                                    self.n_bins, use_pallas=self.use_pallas)
        self._count_launch()
        lazy = lazy.reshape(lazy.shape[0], self.n_bins, n_slots, width)
        return self.cipher.reduce(lazy)

    def _pyobj_hist(self, bins, cts, rows):
        cts = np.asarray(cts, dtype=object)[np.asarray(rows, np.int64)]
        n_f = bins.shape[1]
        n_slots = cts.shape[1]
        hist = self.cipher.zero((n_f, self.n_bins, n_slots))
        add_at = getattr(self.cipher, "add_at", None)
        if add_at is None:          # generic oracle fallback
            for i in range(bins.shape[0]):
                for f in range(n_f):
                    b = bins[i, f]
                    if b < 0:
                        continue
                    hist[f, b] = self.cipher.add(hist[f, b], cts[i])
            return hist
        for f in range(n_f):
            keep = bins[:, f] >= 0
            if keep.any():
                add_at(hist[f], bins[keep, f], cts[keep])
        return hist

    # -- layer-batched accumulation (DESIGN.md §6/§7) ---------------------
    def layer_histograms(self, frontier, node_rows: dict, direct: list,
                         subtract: list, forest: int = 0) -> dict:
        """All frontier histograms of one tree layer in one batch.

        frontier:  a ``core.frontier.CipherFrontier`` — the device-resident
                   layer state: sparse-masked bins and width-padded
                   ciphertext limbs (placed once per tree), plus the cache
                   of canonical parent histograms as device arrays.
        node_rows: {nid: row positions into the frontier's view}.
        direct:    nids accumulated directly -- ONE kernel dispatch for all
                   (``shard_map``-sharded over the engine's mesh when one is
                   set: per-shard kernel + lazy int32 psum over "data",
                   node blocks over "model").
        subtract:  (nid, parent, sibling) triples; the parent's canonical
                   histogram is read from the frontier cache, the sibling
                   must be in ``direct``.  Subtraction happens in the lazy
                   limb domain (``cipher.lazy_sub``) so a SINGLE
                   ``cipher.reduce`` canonicalizes direct and subtracted
                   nodes together.
        forest:    0 for the classic layer path; k > 0 means ``direct`` /
                   ``subtract`` hold gids of a k-member round-forest layer
                   (``gid = member * GID_STRIDE + nid``) and a row may sit
                   in one direct node *per member* — the accumulation runs
                   the (tree, node)-batched kernel, then the member-major
                   result is gathered back into ``direct`` order so every
                   downstream step (lazy subtraction, the single reduce,
                   cumsum, shuffle, compress) is unchanged.
        Returns {nid: (hist, counts)}; the frontier owns cache writes.
        """
        if self.cipher.backend != "limb":
            return self._pyobj_layer(frontier, node_rows, direct, subtract)
        import jax.numpy as jnp
        n_f, n_b = frontier.data.n_features, self.n_bins
        sparse = frontier.sparse
        slot_of = {nid: k for k, nid in enumerate(direct)}

        out = {}
        n_d = len(direct)
        counts = np.zeros((n_d, n_f, n_b), np.int64)
        lazy = None
        node_slot = None
        if n_d and forest:
            slot_mat, member_local, n_local = frontier.layer_slots_forest(
                node_rows, direct, forest, GID_STRIDE)
            if frontier.stream_blocks is not None:
                lazy_f, cnts_all = self._stream_layer(frontier, slot_mat,
                                                      n_local, forest=forest)
                cnts_m = list(cnts_all)
                n_slots = frontier.stream_blocks.cts.shape[1]
                width = self.cipher.hist_width
            else:
                nh = frontier.bins_np.shape[0]
                cnts_m = [np.asarray(layer_count_histogram(
                    frontier.bins_np, slot_mat[:nh, m], n_local,
                    n_b)).astype(np.int64) for m in range(forest)]
                lazy_f = self._forest_dispatch(frontier, slot_mat, n_local,
                                               forest)
                n, n_slots, width = frontier.state.cts.shape
            for kk, gid in enumerate(direct):
                m, loc = member_local[gid]
                counts[kk] = cnts_m[m][loc]
            lazy_f = lazy_f.reshape(forest, n_local, n_f, n_b, n_slots,
                                    width)
            # gather member-major blocks back into flat ``direct`` order
            t_idx = jnp.asarray(np.array(
                [member_local[gid][0] for gid in direct], np.int32))
            s_idx = jnp.asarray(np.array(
                [member_local[gid][1] for gid in direct], np.int32))
            lazy = lazy_f[t_idx, s_idx]      # (n_d, n_f, n_b, slots, width)
            if sparse:
                # global-slot matrix for the zero-bin recovery scatter: one
                # column per member, entries index into ``direct``
                node_slot = np.full((frontier._n_rows_dev, forest), -1,
                                    np.int32)
                for kk, gid in enumerate(direct):
                    node_slot[node_rows[gid], member_local[gid][0]] = kk
        elif n_d:
            node_slot = frontier.layer_slots(node_rows, direct)
            if frontier.stream_blocks is not None:
                lazy, cnts_all = self._stream_layer(frontier, node_slot, n_d)
                counts = cnts_all[0]
                n_slots = frontier.stream_blocks.cts.shape[1]
                width = self.cipher.hist_width
            else:
                # node_slot is aligned with the (possibly mesh-padded)
                # device bins; the plaintext counts run on the unpadded
                # host mirror
                counts = np.asarray(layer_count_histogram(
                    frontier.bins_np, node_slot[: frontier.bins_np.shape[0]],
                    n_d, n_b)).astype(np.int64)
                lazy = self._layer_dispatch(frontier, node_slot, n_d)
                n, n_slots, width = frontier.state.cts.shape
            lazy = lazy.reshape(n_d, n_f, n_b, n_slots, width)

        if sparse:
            # zero-bin recovery needs canonical per-node totals, so fix the
            # direct batch first, then subtract canonically -- still O(1)
            # vectorized cipher calls per layer.
            if n_d:
                with self.tracer.span("carry_fix", nodes=n_d):
                    canon_direct = self.cipher.reduce(lazy)
                canon_direct = self._layer_sparse_fix(
                    frontier.data, canon_direct, frontier.state.cts,
                    node_slot, frontier=frontier)
                zb = np.asarray(frontier.data.zero_bins, np.int64)
                for k, nid in enumerate(direct):
                    for f in range(n_f):
                        counts[k, f, zb[f]] += (len(node_rows[nid])
                                                - counts[k, f].sum())
                    out[nid] = (canon_direct[k], counts[k])
            if subtract:
                # parents are device arrays in the frontier cache: one stack,
                # no per-node host->device copies
                parents = jnp.stack([frontier.hist(par)
                                     for _, par, _ in subtract])
                children = jnp.stack([out[sib][0] for _, _, sib in subtract])
                subs = self.cipher.sub(parents, children)
                for j, (nid, par, sib) in enumerate(subtract):
                    out[nid] = (subs[j], frontier.count(par) - out[sib][1])
            return out

        # dense path: lazy subtraction, one reduce for the whole layer
        sub_lazy = [self.cipher.lazy_sub(frontier.hist(par),
                                         lazy[slot_of[sib]],
                                         len(node_rows[sib]))
                    for _, par, sib in subtract]
        parts = ([lazy] if n_d else []) + \
            ([jnp.stack(sub_lazy)] if sub_lazy else [])
        if not parts:
            return out
        with self.tracer.span("carry_fix", nodes=n_d + len(subtract)):
            canon = self.cipher.reduce(jnp.concatenate(parts, axis=0))
        for k, nid in enumerate(direct):
            out[nid] = (canon[k], counts[k])
        for j, (nid, par, sib) in enumerate(subtract):
            out[nid] = (canon[n_d + j],
                        frontier.count(par) - counts[slot_of[sib]])
        return out

    def _stream_layer(self, frontier, node_slot: np.ndarray, n_nodes: int,
                      forest: int = 0):
        """Out-of-core layer accumulation (DESIGN.md §13): one pass over
        the frontier's row blocks drives the streamed launch path while the
        plaintext counts accumulate in the same pass.  Returns
        ``(lazy, counts)`` where ``lazy`` matches the monolithic dispatch's
        layout ((n_nodes, n_f, n_b, L) or (k, n_nodes, ...) for a forest)
        and ``counts`` is (max(k, 1), n_nodes, n_f, n_b) int64."""
        n_b = self.n_bins
        n_f = frontier.data.n_features
        k = max(forest, 1)
        cnts = np.zeros((k, n_nodes, n_f, n_b), np.int64)
        stats = self.stats
        launches = [0]

        def blocks():
            for bins_blk, slot_blk, cts_blk in \
                    frontier.iter_stream_blocks(node_slot):
                if forest:
                    for m in range(forest):
                        cnts[m] += np.asarray(layer_count_histogram(
                            bins_blk, slot_blk[:, m], n_nodes, n_b),
                            np.int64)
                else:
                    cnts[0] += np.asarray(layer_count_histogram(
                        bins_blk, slot_blk, n_nodes, n_b), np.int64)
                yield bins_blk, slot_blk, cts_blk.reshape(
                    cts_blk.shape[0], -1)

        def on_block(nbytes):
            self._count_launch()
            launches[0] += 1
            if stats is not None:
                stats.peak_block_bytes = max(stats.peak_block_bytes,
                                             int(nbytes))
            self.tracer.instant("stream_block", blk=launches[0] - 1,
                                nbytes=int(nbytes))

        # pow2 node padding: same compile-bucketing as the monolithic path
        n_pad = 1 << max(n_nodes - 1, 0).bit_length()
        multi = self._mesh_devices() > 1
        lazy = streamed_layer_ciphertext_histogram(
            blocks(), n_pad, n_b, forest=forest,
            mesh=self.mesh if multi else None,
            use_pallas=self.use_pallas, on_block=on_block)
        if multi:
            # same analytic collective ledger as the monolithic dispatch,
            # paid once per block
            sizes = dict(self.mesh.shape)
            mm = sizes.get("model", 1)
            npm = -(-n_pad // mm)
            n_slots = frontier.stream_blocks.cts.shape[1]
            shard_bytes = (k * npm * n_f * n_b * n_slots
                           * self.cipher.hist_width * 4)
            for _ in range(launches[0]):
                if sizes.get("data", 1) > 1:
                    frontier.collective(
                        "hist_psum", psum_wire_bytes(self.mesh, shard_bytes))
                if mm > 1:
                    frontier.collective(
                        "hist_allgather",
                        allgather_wire_bytes(self.mesh, shard_bytes * mm))
        lazy = lazy[:, :n_nodes] if forest else lazy[:n_nodes]
        return lazy, cnts

    def _forest_dispatch(self, frontier, slot_mat: np.ndarray, n_local: int,
                         k: int):
        """One (tree, node)-batched accumulation dispatch for a round-forest
        layer: the member axis rides through the kernel grid while the
        member-local node axis keeps the layer dispatch's "model" blocking.
        Returns (k, n_local, n_f, n_b, L) lazy limb sums."""
        state = frontier.state
        n_slots, width = state.cts.shape[1:]
        flat = frontier.cts_flat
        if self.stats is not None:
            self.stats.peak_block_bytes = max(
                self.stats.peak_block_bytes,
                (int(state.bins.size) + int(slot_mat.size)
                 + int(flat.size)) * 4)
        n_pad = 1 << max(n_local - 1, 0).bit_length()
        if self._mesh_devices() > 1:
            lazy = sharded_forest_ciphertext_histogram(
                state.bins, slot_mat, flat, n_pad, self.n_bins, self.mesh,
                use_pallas=self.use_pallas)[:, :n_local]
            sizes = dict(self.mesh.shape)
            mm = sizes.get("model", 1)
            npm = -(-n_pad // mm)
            shard_bytes = (k * npm * frontier.data.n_features * self.n_bins
                           * n_slots * width * 4)
            if sizes.get("data", 1) > 1:
                frontier.collective("hist_psum",
                                    psum_wire_bytes(self.mesh, shard_bytes))
            if mm > 1:
                frontier.collective(
                    "hist_allgather",
                    allgather_wire_bytes(self.mesh, shard_bytes * mm))
        else:
            lazy = forest_ciphertext_histogram(
                state.bins, slot_mat, flat, n_pad, self.n_bins,
                use_pallas=self.use_pallas)[:, :n_local]
        self._count_launch()
        return lazy

    def _layer_dispatch(self, frontier, node_slot: np.ndarray, n_d: int):
        """One accumulation dispatch for the layer's direct nodes: the
        single-device kernel, or the shard_map dispatch (+ lazy-limb psum
        over "data") when the engine carries a multi-device mesh."""
        state = frontier.state
        n_slots, width = state.cts.shape[1:]
        flat = frontier.cts_flat          # flattened once per tree
        if self.stats is not None:
            self.stats.peak_block_bytes = max(
                self.stats.peak_block_bytes,
                (int(state.bins.size) + int(node_slot.size)
                 + int(flat.size)) * 4)
        # pad the node axis to the next power of two: the node count is a
        # static kernel arg, so this caps distinct jit compilations at
        # O(log max_nodes) per tree shape instead of one per frontier size
        n_pad = 1 << max(n_d - 1, 0).bit_length()
        if self._mesh_devices() > 1:
            lazy = sharded_layer_ciphertext_histogram(
                state.bins, node_slot, flat, n_pad, self.n_bins, self.mesh,
                use_pallas=self.use_pallas)[:n_d]
            sizes = dict(self.mesh.shape)
            # bytes reflect the padded node count the dispatch actually
            # moves; axes of extent 1 run no collective and tally nothing
            mm = sizes.get("model", 1)
            npm = -(-n_pad // mm)
            shard_bytes = (npm * frontier.data.n_features * self.n_bins
                           * n_slots * width * 4)
            if sizes.get("data", 1) > 1:
                frontier.collective("hist_psum",
                                    psum_wire_bytes(self.mesh, shard_bytes))
            if mm > 1:
                frontier.collective(
                    "hist_allgather",
                    allgather_wire_bytes(self.mesh, shard_bytes * mm))
        else:
            lazy = layer_ciphertext_histogram(
                state.bins, node_slot, flat, n_pad, self.n_bins,
                use_pallas=self.use_pallas)[:n_d]
        self._count_launch()
        return lazy

    def _pyobj_layer(self, frontier, node_rows, direct, subtract):
        """Paillier-oracle layer path: per-node accumulation (clarity over
        speed -- the protocol round-trip is still batched by the caller)."""
        out = {}
        for nid in direct:
            out[nid] = self.node_histogram(frontier.data, frontier.cts_obj,
                                           node_rows[nid])
        for nid, par, sib in subtract:
            out[nid] = self.subtract((frontier.hist(par),
                                      frontier.count(par)), out[sib])
        return out

    # -- paper tricks -------------------------------------------------------
    def _layer_sparse_fix(self, data, hist, cts_wide, node_slot,
                          frontier=None):
        """Batched §6.2 recovery: per node, zero-bin += total - sum(bins).

        hist: (n_d, n_f, n_b, n_slots, L) canonical; cts_wide: (n, n_slots,
        width) padded limbs aligned with node_slot.  A 2-D node_slot is the
        round-forest global-slot matrix (one column per member: a row
        contributes its ciphertext to up to one node per member tree).
        ``cts_wide is None`` selects the out-of-core path: the per-node
        totals accumulate over the frontier's row blocks (int32 scatter-adds
        are exact and order-free, so the block split is bit-invisible)."""
        import jax
        import jax.numpy as jnp
        from .he import limbs
        n_d = hist.shape[0]
        width = self.cipher.hist_width
        # per-node ciphertext totals: one scatter-add (per member column in
        # forest mode) + one reduce
        if cts_wide is None:
            n_slots = frontier.stream_blocks.cts.shape[1]
            tot_lazy = jnp.zeros((n_d + 1, n_slots, width), jnp.int32)
            for _, slot_blk, cts_blk in \
                    frontier.iter_stream_blocks(node_slot):
                slot_b = np.where(slot_blk < 0, n_d, slot_blk)
                cw = jnp.asarray(cts_blk)
                for col in (slot_b.T if slot_b.ndim == 2 else [slot_b]):
                    tot_lazy = tot_lazy.at[jnp.asarray(col)].add(cw)
        else:
            slot = np.where(node_slot < 0, n_d, node_slot)
            tot_lazy = jnp.zeros((n_d + 1,) + tuple(cts_wide.shape[1:]),
                                 jnp.int32)
            for col in (slot.T if slot.ndim == 2 else [slot]):
                tot_lazy = tot_lazy.at[jnp.asarray(col)].add(cts_wide)
        if self._mesh_devices() > 1:
            # cts live mesh-sharded; land the small per-node totals next to
            # the (single-device) gathered histograms before mixing
            tot_lazy = jax.device_put(tot_lazy, self.mesh.devices.flat[0])
        node_total = self.cipher.reduce(tot_lazy[:n_d])   # (n_d, slots, L)
        nz = self.cipher.reduce(
            limbs.pad_limbs(hist, width).sum(axis=2))     # (n_d, n_f, s, L)
        rec = self.cipher.sub(
            jnp.broadcast_to(node_total[:, None], nz.shape), nz)
        zb = np.asarray(data.zero_bins, np.int64)
        for f in range(hist.shape[1]):
            hist = hist.at[:, f, zb[f]].set(
                self.cipher.add(hist[:, f, zb[f]], rec[:, f]))
        return hist

    def _sparse_fix(self, data: BinnedData, hist, cts, rows):
        """zero-bin += node_total - sum(all accumulated bins)  (§6.2)."""
        node_total = self.node_total(cts, rows)            # (n_slots, ...)
        zb = np.asarray(data.zero_bins, np.int64)
        if self.cipher.backend == "limb":
            import jax.numpy as jnp
            from .he import limbs
            hist = jnp.asarray(hist)
            wide = limbs.pad_limbs(hist, self.cipher.hist_width)
            nz = self.cipher.reduce(wide.sum(axis=1))      # (n_f, n_slots, L)
            rec = self.cipher.sub(
                jnp.broadcast_to(node_total[None], nz.shape), nz)
            for f in range(hist.shape[0]):
                hist = hist.at[f, zb[f]].set(
                    self.cipher.add(hist[f, zb[f]], rec[f]))
            return hist
        n_f = hist.shape[0]
        for f in range(n_f):
            acc = hist[f, 0]
            for b in range(1, self.n_bins):
                acc = self.cipher.add(acc, hist[f, b])
            rec = self.cipher.sub(node_total, acc)
            hist[f, zb[f]] = self.cipher.add(hist[f, zb[f]], rec)
        return hist

    def node_total(self, cts, rows):
        """Sum of all instance ciphertexts in the node: (n_slots, ...)."""
        if self.cipher.backend == "limb":
            import jax.numpy as jnp
            from .he import limbs
            sel = jnp.asarray(cts)[jnp.asarray(np.asarray(rows, np.int64))]
            wide = limbs.pad_limbs(sel, self.cipher.hist_width)
            return self.cipher.reduce(wide.sum(axis=0))
        sel = np.asarray(cts, dtype=object)[np.asarray(rows, np.int64)]
        tot = self.cipher.zero((sel.shape[1],))
        for i in range(sel.shape[0]):
            tot = self.cipher.add(tot, sel[i])
        return tot

    def subtract(self, parent, child):
        """Ciphertext histogram subtraction: sibling = parent - child (§4.3)."""
        ph, pc = parent
        ch, cc = child
        return self.cipher.sub(ph, ch), pc - cc

    def _sharded_cumsum(self, wide, bin_axis: int):
        """Mesh-sharded ciphertext-domain prefix sum over the bin axis.

        The leading (node, feature) axes flatten into one embarrassingly
        parallel row axis sharded over "data": each shard cumsums and
        carry-fixes its rows with NO collective — cumsum and reduce are
        per-row — so the result is bit-identical to the single-device path.
        This closes the last single-device remainder of the layer pipeline
        (accumulate and decrypt were sharded in PRs 2-3; the layer cumsum
        between them still serialized on one device).

        Gated exactly like ``_decrypt_ints``: shard only when every data
        shard gets at least one full kernel row block (shallow layers are
        sub-millisecond and would pay a shard_map compile per pow2 bucket).
        Returns None below the gate; the caller falls back to the
        single-device reduce."""
        if self._mesh_devices() <= 1 or bin_axis < 1:
            return None
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ..kernels.modmul.modmul import BLOCK_N
        from ..parallel.sharding import data_pad, gbdt_sharding
        mesh = self.mesh
        dd = dict(mesh.shape).get("data", 1)
        lead = tuple(wide.shape[:bin_axis])
        G = int(np.prod(lead))
        if dd <= 1 or G < BLOCK_N * dd:
            return None
        tail = tuple(wide.shape[bin_axis:])       # (n_b, slots, width)
        x = wide.reshape((G,) + tail)
        # pow2 bucketing caps distinct compilations at O(log max_G), same
        # rationale as the decrypt stack's candidate padding
        bucket = 1 << max(G - 1, 0).bit_length()
        bucket += data_pad(mesh, bucket)
        if bucket > G:
            x = jnp.pad(x, [(0, bucket - G)] + [(0, 0)] * (x.ndim - 1))
        x = jax.device_put(
            x, gbdt_sharding(mesh, "split_infos", ndim=x.ndim))
        out = jax.shard_map(
            lambda xs: self.cipher.reduce(jnp.cumsum(xs, axis=1)),
            mesh=mesh,
            in_specs=P("data", None, None, None),
            out_specs=P("data", None, None, None))(x)
        # land on the mesh's first device, next to the single-device
        # operands of the shuffle/compress consumers
        out = jax.device_put(out[:G], mesh.devices.flat[0])
        # reduce canonicalizes the limb axis (hist width -> Ln)
        return out.reshape(lead + tuple(out.shape[1:]))

    def cumsum(self, hist):
        """Prefix-sum over the bin axis in the ciphertext domain.  Accepts a
        single histogram (n_f, n_b, slots[, L]) or a layer-batched stack with
        any leading axes (..., n_f, n_b, slots[, L])."""
        if self.cipher.backend == "limb":
            import jax.numpy as jnp
            from .he import limbs
            hist = jnp.asarray(hist)
            wide = limbs.pad_limbs(hist, self.cipher.hist_width)
            bin_axis = hist.ndim - 3
            out = self._sharded_cumsum(wide, bin_axis)
            if out is not None:
                return out
            return self.cipher.reduce(jnp.cumsum(wide, axis=bin_axis))
        flat = hist.reshape((-1,) + hist.shape[-2:])   # (G, n_b, slots)
        out = np.empty(flat.shape, dtype=object)
        for i in range(flat.shape[0]):
            acc = None
            for b in range(flat.shape[1]):
                acc = flat[i, b] if acc is None \
                    else self.cipher.add(acc, flat[i, b])
                out[i, b] = acc
        return out.reshape(hist.shape)
