"""Round-batched federated serving engine (DESIGN.md §9).

The training-time ``predict_tree`` loop walks nodes one at a time in
python, per tree, reading host tables directly — no batching, no protocol
accounting.  This engine serves a whole batch through ALL trees at once
with ONE wire round-trip per host per batch:

1. **Bits.**  Each party evaluates every internal node it owns for every
   instance in one vectorized binning+compare pass, *transposed and
   bit-packed*: ``bits[j, i//8]`` bit ``i%8`` says instance ``i`` goes left
   at node-column ``j``.  The packed uint8 tensor is simultaneously the
   wire payload (1 bit per node per instance — what the byte ledger
   counts) and the routing operand (the fused compare→packbits pass writes
   8x fewer bytes than a bool tensor, which is what makes the engine
   memory-bound-fast on CPU and TPU alike).
2. **Combine.**  The guest concatenates the per-party row blocks — packed
   node ids ARE bit-tensor rows (``serving/packed.py``), so no scatter.
3. **Route.**  A jitted layer-synchronous loop advances an (instance,
   tree) cursor ``depth`` times through the fused ``step[node, bit]``
   table; leaves self-loop.  Embarrassingly parallel over rows — with a
   mesh, the packed byte axis and the cursor row axis shard over "data"
   (rule-table entries ``serve_bits`` / ``serve_route``) with no
   collective.
4. **Accumulate.**  Leaf weights are gathered host-side in float64 and
   summed per tree in training order — bit-identical to the legacy
   ``predict_tree`` path by construction (routing is exact integer work;
   the float adds replay the same sequence).

Wire accounting uses the existing :class:`Channel`/:class:`Stats`
plumbing: ``predict_req`` (guest -> host, instance ids) and
``predict_bits`` (host -> guest, the packed bit block) per host per batch,
``Stats.n_predict_roundtrips`` counting the latter.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import schema as wire
from ..analysis.registry import declassifies
from ..core.binning import BinnedData, apply_binning
from ..core.party import Channel, PartyUnavailable, Stats


@declassifies("one comparison bit per (row, owned node): the serving "
              "protocol's unit of disclosure — never raw feature values")
@jax.jit
def _packed_bits(bins_T, fid, bid):
    """All of one party's decision bits in one fused pass.

    ``bins_T`` (n_f, n_pad) int32 — transposed *on the host* so the
    device sees a contiguous layout whose gathered rows are sequential
    sweeps (transposing inside the jit measured ~2x slower on CPU: XLA
    materializes the transposed gather poorly, while the numpy
    transpose+pad of a cache-resident (n, n_f) block is near-memcpy).
    ``fid``/``bid`` (k,) — the party's split table in bit-column order.
    Returns (k, n_pad // 8) uint8, bitorder little.
    """
    ge = jnp.take(bins_T, fid, axis=0) <= bid[:, None]
    return jnp.packbits(ge, axis=1, bitorder="little")


@partial(jax.jit, static_argnames="depth")
def _route(bits, step, node, depth: int):
    """Layer-synchronous traversal: advance the (instance, tree) cursor
    ``depth`` times.  ``bits`` (k, n_pad/8) uint8; ``step`` (n_nodes, 2)
    int32 with leaves self-looping, so no leaf test is needed and leaf
    cursor entries read a clamped, ignored bit."""
    rows = jnp.arange(node.shape[0], dtype=jnp.int32)[:, None]
    byte_ix = rows >> 3
    shift = (rows & 7).astype(jnp.uint8)
    kmax = max(bits.shape[0] - 1, 0)

    def body(_, node):
        b = (bits[jnp.minimum(node, kmax), byte_ix] >> shift) & 1
        return step[node, b.astype(jnp.int32)]

    return jax.lax.fori_loop(0, depth, body, node)


class PartyBits:
    """One party's serving evaluator: bins its OWN features and computes
    its decision-bit block (the fused compare→packbits pass).

    This is exactly the computation a host's ``PartyProcess`` runs on its
    side of the socket under ``runtime/transport.py`` — in-process serving
    calls the same object directly, so the two modes are bit-identical by
    construction."""

    def __init__(self, table, thresholds, n_bins: int, use_pallas: bool):
        self.table = table
        self.use_pallas = use_pallas
        # binner view: reuses the BinnedData device-threshold cache
        self.binner = BinnedData(
            bins=np.zeros((0, thresholds.shape[0]), np.int32),
            thresholds=thresholds, n_bins=n_bins)
        self._fid = jnp.asarray(table.fid)
        self._bid = jnp.asarray(table.bid)

    def bin(self, X) -> np.ndarray:
        return apply_binning(X, self.binner, self.use_pallas)

    def packed(self, bins: np.ndarray, n_pad: int):
        """(k, n_pad // 8) uint8 decision bits for one binned batch."""
        bins_T = np.zeros((bins.shape[1], n_pad), np.int32)
        bins_T[:, : bins.shape[0]] = bins.T
        return _packed_bits(jnp.asarray(bins_T), self._fid, self._bid)

    @declassifies("wrapper over _packed_bits: bins then packs to the "
                  "one-bit-per-node disclosure unit")
    def packed_from_X(self, X, n_pad: int):
        return self.packed(self.bin(X), n_pad)


class FederatedPredictor:
    """Serves batched predictions from packed per-party halves.

    Works from a live ``VerticalBoosting`` (see
    ``VerticalBoosting.predict_score``), from halves reloaded by
    ``serving/export.py`` — a serving process never needs the training
    objects — or from ``RemoteServingHost`` handles whose half lives in
    another OS process (``runtime/transport.py``).  All cross-party
    transfers go through ``channel`` with protocol-fidelity byte counts
    under the ``predict_*`` tags.
    """

    def __init__(self, guest, hosts, *, channel: Channel | None = None,
                 stats: Stats | None = None, mesh=None,
                 use_pallas: bool = True):
        hosts = sorted(hosts, key=lambda h: h.hid)
        if len(hosts) != guest.n_hosts or any(
                h.hid != i for i, h in enumerate(hosts)):
            raise ValueError(
                f"guest half expects hosts 0..{guest.n_hosts - 1}, got "
                f"{[h.hid for h in hosts]}")
        if guest.guest.k != int(guest.k_parties[0]):
            raise ValueError(
                f"guest split table has {guest.guest.k} nodes, k_parties "
                f"records {int(guest.k_parties[0])}")
        for h in hosts:
            k = h.table.k if hasattr(h, "table") else h.k
            if k != int(guest.k_parties[1 + h.hid]):
                raise ValueError(
                    f"host{h.hid} table has {k} nodes, guest half "
                    f"expects {int(guest.k_parties[1 + h.hid])}")
        self.guest = guest
        self.hosts = hosts
        self.channel = channel if channel is not None else Channel()
        self.stats = stats if stats is not None else Stats()
        self.mesh = mesh if (mesh is not None
                             and mesh.devices.size > 1) else None
        # serving is latency-sensitive: take the Pallas bucketize only
        # where it compiles natively (TPU).  Off-TPU it would run in
        # interpret mode — python per grid tile — while the pure-jnp ref
        # is bit-identical (tested) and XLA-compiled everywhere.
        from ..kernels.common import default_interpret
        self.use_pallas = use_pallas and not default_interpret()

        self._step = jnp.asarray(guest.step)
        # per party: a PartyBits evaluator (in-process halves), or None
        # for parties owning no internal nodes, or a remote handle whose
        # process evaluates its own bits (``RemoteServingHost``)
        self._bits = [PartyBits(guest.guest, guest.thresholds, guest.n_bins,
                                self.use_pallas)
                      if guest.guest.k else None]
        for h in hosts:
            if hasattr(h, "table"):     # in-process HostHalf
                self._bits.append(
                    PartyBits(h.table, h.thresholds, h.n_bins,
                              self.use_pallas) if h.table.k else None)
            else:                       # remote: its PartyProcess computes
                self._bits.append(h if h.k else None)

    # ------------------------------------------------------------------
    def predict_score(self, X_guest, X_hosts) -> np.ndarray:
        """Raw ensemble scores for one batch (one round-trip per host).

        With remote hosts the corresponding ``X_hosts`` entries are
        ignored (pass None): each host process bins its OWN feature
        matrix and answers the ``predict_req`` with its bit block."""
        if len(X_hosts) != len(self.hosts):
            raise ValueError(f"expected {len(self.hosts)} host matrices, "
                             f"got {len(X_hosts)}")
        # a guest owning no internal nodes (e.g. layered mode) never needs
        # its bins — only the batch row count
        guest_bins = (self._bits[0].bin(X_guest)
                      if self._bits[0] is not None
                      else np.zeros((len(X_guest), 0), np.int32))
        return self._predict_core(guest_bins, list(X_hosts), binned=False)

    def predict_proba(self, X_guest, X_hosts) -> np.ndarray:
        from ..core.loss import sigmoid, softmax
        s = self.predict_score(X_guest, X_hosts)
        return sigmoid(s) if self.guest.objective == "binary" else softmax(s)

    def predict_score_binned(self, guest_bins: np.ndarray,
                             host_bins: list) -> np.ndarray:
        """Serve one already-binned batch: the engine entry point shared by
        ``predict_score`` and the from-bins benchmark.  In-process halves
        only: a remote host bins its OWN staged rows, so caller-supplied
        bins for it would be silently ignored — refuse instead."""
        if any(b is not None and not isinstance(b, PartyBits)
               for b in self._bits[1:]):
            raise ValueError(
                "predict_score_binned serves in-process halves only; "
                "remote hosts bin their own staged rows — use "
                "predict_score / MultiHostRun.predict_score")
        return self._predict_core(guest_bins, list(host_bins), binned=True)

    def _predict_core(self, guest_bins: np.ndarray, host_parts: list,
                      binned: bool) -> np.ndarray:
        g = self.guest
        t0 = time.perf_counter_ns()
        if len(host_parts) != len(self.hosts):
            raise ValueError(f"expected {len(self.hosts)} host matrices, "
                             f"got {len(host_parts)}")
        n = guest_bins.shape[0]
        self.stats.n_predict_batches += 1
        tracer = self.channel.tracer

        # pad instances to the next power of two, then to the packed-byte
        # granule (x mesh data extent when sharded).  The pow2 bucketing
        # caps distinct jit compilations of the bits/route kernels at
        # O(log max_batch) across varying batch sizes — the same retrace
        # bound the training path uses for candidate stacks (DESIGN.md
        # §8).  Pad rows route garbage and are sliced off before the
        # weight gather.
        dext = 1
        if self.mesh is not None:
            sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
            dext = int(np.prod([sizes.get(a, 1)
                                for a in ("pod", "data") if a in sizes]))
        n_pad = 1 << max(n - 1, 1).bit_length()
        n_pad += (-n_pad) % (8 * dext)

        blocks = []
        if self._bits[0] is not None:
            with tracer.span("serve_bins", cat="serve", rows=int(n)):
                blocks.append(self._bits[0].packed(guest_bins, n_pad))
        # one round-trip per host per batch: the request carries the
        # instance ids (+ the pad extent so both sides bucket alike), the
        # reply the packed bit block.  ALL requests go out before any
        # reply is collected, so remote hosts compute their bit blocks
        # concurrently (latency = max over hosts, not the sum) — the same
        # dispatch-then-collect shape as the training layer batch.
        with tracer.span("serve_roundtrip", cat="serve",
                         hosts=len(self.hosts)):
            blocks += self._host_bits(n, n_pad, host_parts, binned)

        if blocks and g.depth > 0:
            with tracer.span("serve_route", cat="serve", rows=int(n),
                             trees=int(g.n_trees)):
                bits = (blocks[0] if len(blocks) == 1
                        else jnp.concatenate(blocks, axis=0))
                node0 = jnp.broadcast_to(jnp.asarray(g.roots),
                                         (n_pad, g.n_trees))
                if self.mesh is not None:
                    from ..parallel.sharding import gbdt_sharding
                    bits = jax.device_put(
                        bits, gbdt_sharding(self.mesh, "serve_bits"))
                    node0 = jax.device_put(
                        node0, gbdt_sharding(self.mesh, "serve_route"))
                node = np.asarray(_route(bits, self._step, node0,
                                         g.depth))[:n]
        else:                               # every tree is a lone leaf
            node = np.broadcast_to(g.roots, (n, g.n_trees))

        # float accumulation replays the legacy per-tree order exactly
        with tracer.span("serve_leaf_sum", cat="serve", rows=int(n),
                         trees=int(g.n_trees)):
            w = g.leaf_w[node]              # (n, n_trees, w_dim)
            if g.objective == "binary":
                score = np.full(n, g.init_score)
                for t in range(g.n_trees):
                    score += w[:, t, 0]
            elif g.objective == "multiclass":
                score = np.tile(g.init_score, (n, 1))
                for t in range(g.n_trees):
                    score[:, g.tree_class[t]] += w[:, t, 0]
            else:                           # mo: vector leaves
                score = np.tile(g.init_score, (n, 1))
                for t in range(g.n_trees):
                    score += w[:, t]
        # "serve_batch" is the benchmark harness's own annotation: a
        # record in the ring only (obs.trace.RESERVED)
        tracer.complete("serve_batch", t0, time.perf_counter_ns() - t0,
                        cat="serve", rows=int(n))
        return score

    def _host_bits(self, n: int, n_pad: int, host_parts: list,
                   binned: bool) -> list:
        """One round-trip per host: every request goes out, then every
        reply (a packed bit block) is collected."""
        blocks = []
        pending = []                        # (block slot, party, i)
        down: list = []                     # typed per-party failures
        # ONE request object for all hosts: the transport's broadcast
        # memo then encodes the id vector once, not once per host
        req = {"ids": np.arange(n, dtype=np.int32), "n_pad": int(n_pad)}
        for i, h in enumerate(self.hosts):
            party = self._bits[1 + i]
            if party is None:
                continue                    # party owns no internal nodes
            try:
                self.channel.send("guest", f"host{h.hid}", wire.PREDICT_REQ,
                                  req, n * 4)
            except PartyUnavailable as e:
                # keep dispatching: every HEALTHY host must still get its
                # request so the collect pass below consumes its reply —
                # otherwise a stale bit block would sit in the stream and
                # poison the NEXT batch's collect
                down.append(e)
                continue
            if isinstance(party, PartyBits):
                # in-process half: compute (async jax dispatch) and record
                # the reply send here, exactly the oracle accounting
                pb = (party.packed(host_parts[i], n_pad) if binned
                      else party.packed_from_X(host_parts[i], n_pad))
                k = pb.shape[0]
                pb = self.channel.send(f"host{h.hid}", "guest",
                                       wire.PREDICT_BITS, pb,
                                       k * ((n + 7) // 8))
                pending.append(pb)
            else:
                pending.append(party)       # remote: collect below
        for item in pending:
            try:
                pb = item.predict_bits() if hasattr(item, "predict_bits") \
                    else item
            except PartyUnavailable as e:
                down.append(e)
                continue
            self.stats.n_predict_roundtrips += 1
            blocks.append(pb)
        if down:
            # the whole batch fails, typed, after every live host's reply
            # was consumed: never a hang, never an answer scored from a
            # subset of the parties' bits
            raise down[0]
        return blocks
