"""Layer-wise federated decision-tree growth (paper §2.3, Algorithms 1-6).

One function, :func:`grow_tree`, implements the whole node-splitting
protocol with every optimization toggleable (so the legacy SecureBoost
baseline and every ablation in the paper's figures run through the same
code):

  * GH packing on/off        (packed single ciphertext vs separate [[g]],[[h]])
  * histogram subtraction    (compute smaller child, sibling = parent - child)
  * cipher compressing       (eta_s split-infos per decrypted package)
  * sparse-aware histograms  (zero-bin recovery)
  * MO trees                 (vector g/h, multi-class packing)
  * mix / layered modes      (via the ``feature_parties`` schedule callback)

The hot path is *layer-batched* (DESIGN.md §6): per (layer, host) pair the
protocol performs ONE histogram kernel launch covering every direct-mode
frontier node, ONE ``cipher.reduce``, ONE ciphertext cumsum, and ONE
``split_infos`` message answered by ONE batched guest decrypt -- all nodes'
shuffled candidates travel concatenated, with per-node offsets implied by
the fixed per-node candidate count.  Kernel launches and round-trips per
tree are therefore O(depth), not O(2**depth); ``Stats.n_hist_launches`` /
``Stats.n_split_roundtrips`` make the collapse measurable.

Layer state is *device-resident* (DESIGN.md §7): each host builds a
``CipherFrontier`` per tree (bins masked + ciphertexts width-padded once,
parent histograms cached as device arrays) and, when the engine carries a
(data, model) mesh, the single layer dispatch is ``shard_map``-sharded with
a lazy-limb psum over instance shards -- bit-identical to one device.

Party boundaries are explicit: everything that crosses guest<->host goes
through ``ctx.channel.send`` with wire-fidelity byte counts, and HE work is
tallied in ``ctx.stats``.

Host-side protocol logic lives in :class:`HostRuntime` (DESIGN.md §10):
every guest->host message is a *serializable* payload (numpy/limb tensors,
ints, small dicts — never live Python objects), handled by
``HostRuntime.deliver(tag, payload)``, and every host->guest reply is
emitted through ``channel.send`` and picked up with ``collect(tag)``.  The
grower only talks to hosts through this tagged-message surface, so the same
code runs in-process (``HostRuntime`` is the handle, the shared
:class:`Channel` is the ledger) or one-party-per-OS-process
(``runtime/transport.py`` ships the identical payloads over a
length-prefixed socket and the handle becomes a ``RemoteHostHandle``) —
bit-identically, with identical per-tag ledgers.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import numpy as np

from ..analysis import schema as wire
from . import compress as compress_mod
from . import encoding, mo_encoding
from .binning import BinnedData
from .frontier import CipherFrontier, CtsBlocks, GuestFrontier
from .he import limbs
from .histogram import GID_STRIDE, CipherHistogram, PlainHistogram
from .party import Channel, Stats, ct_wire_bytes
from .split import (BestSplit, SplitCandidates, candidates_from_cumsum,
                    decode_sid, find_best_split, leaf_weight)

GUEST = -1
LEAF = -2


# ---------------------------------------------------------------------------
# GH codecs: how (g, h) become plaintexts and come back as sums
# ---------------------------------------------------------------------------

class PackedCodec:
    """SecureBoost+ default: one packed plaintext per instance (Alg 3/6)."""

    def __init__(self, plan: encoding.PackingPlan):
        self.plan = plan
        self.n_slots = 1
        self.compressible = True
        self.b_slot = plan.b_gh
        self.eta_s = plan.compress_capacity
        self.span_attrs = {}

    decode_device = None         # candidates decode from host words

    def encode_plain(self, g, h) -> np.ndarray:
        return encoding.pack_gh(g, h, self.plan)[:, None, :]   # (n, 1, Lp)

    def decode(self, ints: np.ndarray, counts: np.ndarray):
        g_l = np.empty(len(counts)); h_l = np.empty(len(counts))
        for i, (row, c) in enumerate(zip(ints, counts)):
            g_l[i], h_l[i] = encoding.unpack_gh_int(int(row[0]), self.plan, int(c))
        return g_l, h_l

    def decode_limbs(self, plain: np.ndarray, counts: np.ndarray, sizes):
        """:meth:`decode` of the candidates in decrypted plaintext limbs
        (packages when ``sizes`` is given), bit for bit, with no python
        ints; None where a field is wider than the word reader takes."""
        p = self.plan
        if sizes is None:
            # one package a candidate: g is every bit above h, as in
            # ``x >> b_h``
            g_bits = max(p.b_g, encoding.limb_top_bit(plain) - p.b_h)
            if max(g_bits, p.b_h) > encoding.FIELD_BITS_MAX:
                return None
            words = encoding.limb_words(plain, p.b_h + g_bits)
            g_f, h_f = encoding.gh_fields(words, [0], p, g_bits)
            return encoding.unpack_gh_floats(g_f[:, 0], h_f[:, 0], p, counts)
        if max(p.b_g, p.b_h) > encoding.FIELD_BITS_MAX:
            return None
        # ``decompress_ints(padded=True)``: eta_s slots a package, the
        # first most significant; the last group's pad slots are dropped
        slots = np.arange(self.eta_s)
        words = encoding.limb_words(plain, self.eta_s * self.b_slot)
        g_f, h_f = encoding.gh_fields(
            words, (self.eta_s - 1 - slots) * self.b_slot, p)
        keep = slots < np.asarray(sizes)[:, None]
        return encoding.unpack_gh_floats(g_f[keep], h_f[keep], p, counts)


class NoPackCodec:
    """Legacy SecureBoost: separate [[g]] and [[h]] ciphertexts."""

    def __init__(self, r: int, g_off: float):
        self.r = r
        self.g_off = g_off
        self.n_slots = 2
        self.compressible = False
        self.span_attrs = {}

    decode_device = None

    @classmethod
    def plan(cls, g, r: int = encoding.DEFAULT_PRECISION):
        return cls(r=r, g_off=float(max(0.0, -float(np.min(g))))
                   if np.size(g) else 0.0)

    def encode_plain(self, g, h) -> np.ndarray:
        g_int = encoding.encode_int64(np.asarray(g, np.float64) + self.g_off, self.r)
        h_int = encoding.encode_int64(h, self.r)
        L = limbs.num_limbs_for_bits(70)
        out = np.stack([encoding._int64_to_limbs(g_int, L),
                        encoding._int64_to_limbs(h_int, L)], axis=1)
        return out                                              # (n, 2, L)

    def decode(self, ints: np.ndarray, counts: np.ndarray):
        scale = float(1 << self.r)
        g_l = np.asarray([int(r[0]) for r in ints], np.float64) / scale \
            - self.g_off * np.asarray(counts, np.float64)
        h_l = np.asarray([int(r[1]) for r in ints], np.float64) / scale
        return g_l, h_l

    def decode_limbs(self, plain: np.ndarray, counts: np.ndarray, sizes):
        top = encoding.limb_top_bit(plain)
        if top > encoding.FIELD_BITS_MAX:
            return None
        words = encoding.limb_words(plain, top)
        f = encoding.field_float64(words, [0], top).reshape(-1, 2)
        scale = float(1 << self.r)
        return (f[:, 0] / scale - self.g_off * np.asarray(counts, np.float64),
                f[:, 1] / scale)


class MOCodec:
    """SecureBoost-MO: vector g/h packed across classes (Alg 7/8)."""

    def __init__(self, plan: mo_encoding.MOPackingPlan):
        self.plan = plan
        self.n_slots = plan.n_k
        self.compressible = False    # paper §7.3.2: compress disabled for MO
        # the packing the run chose (eqs 21-22), on the encode/decode spans
        self.span_attrs = {"n_classes": plan.n_classes, "eta_c": plan.eta_c,
                           "n_k": plan.n_k}

    def encode_plain(self, G, H) -> np.ndarray:
        return mo_encoding.pack_gh_mo(G, H, self.plan)          # (n, n_k, Lp)

    def decode(self, ints: np.ndarray, counts: np.ndarray):
        l = self.plan.n_classes
        g_l = np.empty((len(counts), l)); h_l = np.empty((len(counts), l))
        for i, (row, c) in enumerate(zip(ints, counts)):
            g_l[i], h_l[i] = mo_encoding.unpack_gh_mo_ints(
                [int(x) for x in row], self.plan, int(c))
        return g_l, h_l

    def decode_limbs(self, plain: np.ndarray, counts: np.ndarray, sizes):
        plan, base = self.plan, self.plan.base
        if max(base.b_g, base.b_h) > encoding.FIELD_BITS_MAX:
            return None
        # ``unpack_gh_mo_ints``: a ciphertext's first class most significant
        cts = plain.reshape(-1, plan.n_k, plain.shape[-1])
        g_f, h_f = [], []
        for k in range(plan.n_k):
            used = plan.slots_in_ct(k)
            words = encoding.limb_words(cts[:, k], used * base.b_gh)
            g, h = encoding.gh_fields(
                words, (used - 1 - np.arange(used)) * base.b_gh, base)
            g_f.append(g); h_f.append(h)
        return encoding.unpack_gh_floats(
            np.concatenate(g_f, axis=1), np.concatenate(h_f, axis=1), base,
            np.asarray(counts)[:, None])

    def decode_device(self, plain, counts):
        """:meth:`decode_limbs` of plaintext limbs still on the device, bit
        for bit: every class's g and h field becomes float64 bits there
        (``encoding.fields_float64_bits``, in row blocks), so 16 bytes a
        class cross to the host, not the limbs; None where a field is wider
        than the bit reader takes."""
        plan, base = self.plan, self.plan.base
        if max(base.b_g, base.b_h) > encoding.FIELD_BITS_MAX:
            return None
        M, l = len(counts), plan.n_classes
        x = plain.reshape(M, -1)          # a candidate's n_k plaintexts a row
        # ``unpack_gh_mo_ints``: class c in slot c % eta_c of ciphertext
        # c // eta_c, a ciphertext's first class most significant
        k, s = np.divmod(np.arange(l), plan.eta_c)
        used = np.asarray([plan.slots_in_ct(i) for i in k])
        h_off = (k * (x.shape[1] // plan.n_k) * limbs.RADIX_BITS
                 + (used - 1 - s) * base.b_gh)
        off = np.concatenate([h_off + base.b_h, h_off]).astype(np.int32)
        width = np.repeat(np.int32([base.b_g, base.b_h]), l)
        bits = limbs.by_row_blocks(
            lambda a: encoding.fields_float64_bits(a, off, width), x,
            limbs.ROW_BLOCK // plan.n_k)
        f = encoding.float64_of_bits(bits)          # (2 l, M): g rows, h rows
        return encoding.unpack_gh_floats(f[:l].T, f[l:].T, base,
                                         np.asarray(counts)[:, None])


# ---------------------------------------------------------------------------
# runtime state
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Node:
    nid: int
    depth: int
    party: int = LEAF            # GUEST / host id / LEAF
    fid: int = -1                # guest splits only (host fids stay private)
    bid: int = -1
    sid: int = -1                # host splits: shuffled id (host resolves)
    left: int = -1
    right: int = -1
    weight: np.ndarray | float | None = None
    gain: float = 0.0
    n_rows: int = 0

    @property
    def is_leaf(self) -> bool:
        return self.party == LEAF


@dataclasses.dataclass
class FederatedTree:
    nodes: list
    host_tables: list            # per host: {nid: (fid, bid)} -- host-private

    def node_arrays(self) -> dict:
        """Flat per-node arrays for the serving packer (serving/packed.py).

        Returns structure (party/left/right/depth), the guest's own
        (fid, bid) pairs, and the (n_nodes, w_dim) leaf-weight matrix
        (zeros at internal nodes).  Host split tables stay in
        ``host_tables`` — they are exported by the *host* half only.
        Deliberately contains nothing row-level: a packed model must be
        shippable to a serving process with no training-set residue.
        """
        nodes = self.nodes
        n = len(nodes)
        party = np.fromiter((nd.party for nd in nodes), np.int32, n)
        left = np.fromiter((nd.left for nd in nodes), np.int32, n)
        right = np.fromiter((nd.right for nd in nodes), np.int32, n)
        depth = np.fromiter((nd.depth for nd in nodes), np.int32, n)
        fid = np.fromiter((nd.fid for nd in nodes), np.int32, n)
        bid = np.fromiter((nd.bid for nd in nodes), np.int32, n)
        first_w = next(np.asarray(nd.weight, np.float64)
                       for nd in nodes if nd.weight is not None)
        weight = np.zeros((n, first_w.size), np.float64)
        for nd in nodes:
            if nd.weight is not None:
                weight[nd.nid] = np.asarray(nd.weight,
                                            np.float64).reshape(-1)
        return {"party": party, "left": left, "right": right,
                "depth": depth, "fid": fid, "bid": bid, "weight": weight}

    def signature(self) -> tuple:
        """Hashable, exact digest of the tree: structure, guest splits,
        host shuffled split ids, and the raw float64 leaf-weight bits.
        Two trees are bit-identical iff their signatures are equal — the
        equality the fault-tolerant runtime's replay guarantee is stated
        in (a resumed run must produce THIS tuple, not merely a close
        one), and what the chaos suite asserts against the fault-free
        oracle."""
        return tuple(
            (nd.nid, nd.depth, nd.party, nd.fid, nd.bid, nd.sid,
             nd.left, nd.right,
             None if nd.weight is None else
             np.asarray(nd.weight, np.float64).tobytes())
            for nd in self.nodes)


@dataclasses.dataclass
class HostRuntime:
    """One host party: private data + the host side of the protocol.

    In-process, the instance doubles as the guest's handle — ``deliver``
    runs the handler synchronously and ``collect`` pops the reply the
    handler emitted.  Under ``runtime/transport.py`` the same instance runs
    inside the host's own OS process, driven by decoded socket frames, and
    the guest holds a ``RemoteHostHandle`` with the identical
    deliver/collect surface.  All handler inputs and all replies are
    serializable (numpy/limb tensors + plain python), never shared live
    objects.
    """

    hid: int
    data: BinnedData
    engine: object               # CipherHistogram (fresh per tree)
    cts: object = None           # (n_sel, n_slots, L) limbs / (n_sel, n_slots) obj
    frontier: CipherFrontier | None = None   # device-resident layer state:
                                     # the GOSS-selected view + padded cts +
                                     # parent-histogram cache (DESIGN.md §7)
    perms: dict = dataclasses.field(default_factory=dict)
    table: dict = dataclasses.field(default_factory=dict)
    params: object = None        # wired by bind()
    cipher: object = None
    channel: Channel | None = None
    stats: Stats | None = None
    codec: object = None         # packing view from the enc_gh payload
    shuffle_rng: object = None   # host-PRIVATE split-id shuffle stream
    table_sinks: dict | None = None   # round-forest demux: member ->
                                 # per-member split table mirror (wired by a
                                 # PartyProcess so serving export sees local
                                 # nids per member tree; None in-process)
    _outbox: dict = dataclasses.field(default_factory=dict)
    _asm: dict | None = None     # in-flight chunked enc_gh assembly (§13)

    # -- wiring ---------------------------------------------------------
    def bind(self, params, cipher, channel, stats) -> None:
        """Attach the run context.  In-process these are the guest's own
        objects (one shared ledger/stats, as always); in a PartyProcess
        they are the host's private instances."""
        self.params, self.cipher = params, cipher
        self.channel, self.stats = channel, stats

    def deliver(self, tag: str, payload) -> None:
        {wire.ENC_GH: self.begin_tree,
         wire.ASSIGN_SYNC: self.on_assign_sync,
         wire.CHOSEN_SID: self.on_chosen_sid}[tag](payload)

    def collect(self, tag: str):
        """Pop the pending reply the last handler emitted for ``tag``."""
        return self._outbox[tag].pop(0)

    def _reply(self, tag: str, payload, nbytes: int) -> None:
        self.channel.send(f"host{self.hid}", "guest", tag, payload, nbytes)
        self._outbox.setdefault(tag, []).append(payload)

    # -- handlers (Algorithm 5, host side) ------------------------------
    def begin_tree(self, msg: dict) -> None:
        """enc_gh: adopt the encrypted GH batch, restrict the binned view
        to the synced selected ids so row positions align with the
        ciphertext batch, and build the device-resident frontier.

        A chunked frame (``"blk" in msg``, DESIGN.md §13) carries one row
        block of the batch; blocks assemble host-side into a compact uint8
        :class:`CtsBlocks` and the frontier is built in stream mode once
        the last block lands.  blk 0 is the replay anchor: a re-delivered
        sequence restarts assembly idempotently, matching the monolithic
        frame's re-delivery semantics."""
        with self.channel.tracer.span("host_begin_tree",
                                      rows=len(msg["sel_rows"])):
            if "blk" in msg:
                self._begin_tree_block(msg)
            else:
                self._adopt_tree(msg, np.asarray(msg["sel_rows"]),
                                 msg["cts"])

    def _begin_tree_block(self, msg: dict) -> None:
        b = int(msg["blk"])
        tree = int(msg["tree"])
        if b == 0:
            cts0 = np.asarray(msg["cts"])
            n = int(msg["n_rows"])
            self._asm = {
                "tree": tree, "msg0": msg,
                "sel": np.zeros(n, np.int64),
                "blocks": CtsBlocks(n, cts0.shape[1], cts0.shape[2],
                                    int(msg["row_block"])),
            }
        elif self._asm is None or self._asm["tree"] != tree:
            return        # duplicate mid-tree block after completion: drop
        asm = self._asm
        sel_blk = np.asarray(msg["sel_rows"])
        start = b * asm["blocks"].block
        asm["sel"][start: start + len(sel_blk)] = sel_blk
        asm["blocks"].set_block(b, np.asarray(msg["cts"], np.uint8))
        if asm["blocks"].complete:
            self._asm = None
            self._adopt_tree(asm["msg0"], asm["sel"], asm["blocks"])

    def _adopt_tree(self, msg: dict, sel: np.ndarray, cts) -> None:
        import types
        self.codec = types.SimpleNamespace(**msg["codec"])
        # host-private shuffle stream: deterministic per (seed, tree, hid)
        # so an in-process run and a process-per-party run permute split
        # ids identically without the stream ever crossing the wire
        self.shuffle_rng = np.random.default_rng(
            (int(msg["seed"]), 23, int(msg["tree"]), self.hid))
        self.cts = cts
        self.perms = {}
        self.table = {}
        n_all = self.data.bins.shape[0]
        if isinstance(cts, CtsBlocks) and len(sel) == n_all \
                and np.array_equal(sel, np.arange(n_all, dtype=sel.dtype)):
            # identity selection (no GOSS): skip the O(rows) fancy-index
            # copy of the compact bin matrix in stream mode
            view = self.data
        else:
            view = dataclasses.replace(
                self.data, bins=self.data.bins[sel],
                zero_mask=(self.data.zero_mask[sel]
                           if self.data.zero_mask is not None else None))
        self.frontier = CipherFrontier(self.engine, view, self.cts,
                                       channel=self.channel,
                                       party=f"host{self.hid}")
        if self.stats is not None:
            self.stats.n_cts_placements += self.frontier.n_cts_placements

    def on_assign_sync(self, plan: dict) -> None:
        """One layer, batched: one histogram accumulation, one
        ``cipher.reduce``, one ciphertext cumsum, one shuffle/compress
        pass, ONE ``split_infos`` reply.  On limb backends everything is
        async dispatch — in-process the guest's plaintext histograms run
        while this work is in flight; across processes the overlap is
        physical."""
        p = self.params
        splittable = [int(nid) for nid in plan["splittable"]]
        forest = int(plan.get("forest", 0) or 0)

        # prune the parent-histogram cache to exactly this layer's
        # subtract parents — BEFORE the empty-layer return, so an
        # all-leaf layer frees the previous layer's cache just like the
        # guest-side eviction loop does: a remote host never sees that
        # loop, the plan itself is its eviction schedule (in-process this
        # is a no-op shadow of the guest's eviction)
        if self.frontier is not None:
            keep = ({int(par) for _, mode, par, _ in plan["modes"]
                     if mode == "subtract"}
                    if p.histogram_subtraction else set())
            size = self.frontier.evict_except(keep)
            # gauge, not counter (max-merged across parties): in-process
            # the guest's end-of-layer measurement already dominates it
            self.stats.peak_hist_cache = max(self.stats.peak_hist_cache,
                                             size)
        if not splittable:
            return
        with self.channel.tracer.span("host_layer",
                                      tree=int(plan.get("tree", -1)),
                                      nodes=len(splittable)):
            self._layer_batch(plan, splittable, forest)

    def _layer_batch(self, plan: dict, splittable: list,
                     forest: int) -> None:
        p = self.params
        codec, cipher = self.codec, self.cipher
        engine = self.engine
        node_of = np.asarray(plan["node_of"])
        hist_mode = {int(nid): (mode, int(par), int(sib))
                     for nid, mode, par, sib in plan["modes"]}
        n_f, n_b = self.data.n_features, p.n_bins
        n_slots = codec.n_slots

        limb = cipher.backend == "limb"
        if limb:
            import jax.numpy as jnp

        direct, subtract = _resolve_modes(splittable, hist_mode,
                                          self.frontier,
                                          p.histogram_subtraction)
        if forest:
            # round-forest plan: node_of is (n_sel, k) and node ids are gids
            node_rows = {nid: np.where(node_of[:, nid // GID_STRIDE]
                                       == nid)[0] for nid in splittable}
        else:
            node_rows = {nid: np.where(node_of == nid)[0]
                         for nid in splittable}
        hists = self.frontier.layer_histograms(node_rows, direct, subtract,
                                               forest=forest)
        for nid in direct:
            self.stats.n_hom_add += int(hists[nid][1].sum()) * n_slots
        self.stats.n_hom_add += len(subtract) * n_f * n_b * n_slots

        # batched cumsum over the node axis, then per-node shuffle + concat
        # (histograms are already device arrays -- no host round-trip)
        if limb:
            stack = jnp.stack([hists[nid][0] for nid in splittable])
        else:
            stack = np.stack([hists[nid][0] for nid in splittable])
        cum = engine.cumsum(stack)
        self.stats.n_hom_add += len(splittable) * n_f * (n_b - 1) * n_slots

        m = n_f * (n_b - 1)          # candidates per node (fixed)
        fid_grid, bid_grid = np.meshgrid(np.arange(n_f), np.arange(n_b - 1),
                                         indexing="ij")
        real_sids = (fid_grid * n_b + bid_grid).reshape(-1)
        flats, counts_l = [], []
        for k, nid in enumerate(splittable):
            # flatten to split infos, drop last bin (empty right side)
            if limb:
                flat = cum[k][:, : n_b - 1].reshape(m, n_slots, -1)
            else:
                flat = cum[k][:, : n_b - 1].reshape(m, n_slots)
            fc = hists[nid][1].cumsum(axis=1)[:, : n_b - 1].reshape(-1)
            # real sids use the same fid*n_b+bid encoding as decode_sid
            perm = self.shuffle_rng.permutation(m)
            self.perms[nid] = real_sids[perm]  # shuffled position -> real sid
            if limb:
                flat = flat[jnp.asarray(perm)]
            else:
                flat = flat[perm]
            flats.append(flat)
            counts_l.append(fc[perm])
        self.stats.n_split_infos += m * len(splittable)
        flat_all = (jnp.concatenate(flats, axis=0) if limb
                    else np.concatenate(flats, axis=0))
        counts_all = np.concatenate(counts_l)
        M = m * len(splittable)

        ct_bytes = ct_wire_bytes(cipher)
        use_compress = (p.compression and codec.compressible
                        and codec.eta_s > 1)
        if use_compress:
            eta = codec.eta_s
            src = flat_all[:, 0, :] if limb else flat_all[:, 0]
            pkgs, sizes = compress_mod.compress_batch(
                cipher, src, eta, codec.b_slot,
                mesh=getattr(engine, "mesh", None))
            n_pkgs = len(sizes)
            self.stats.n_hom_scalar += int(np.sum(sizes - 1))
            self.stats.n_hom_add += int(np.sum(sizes - 1))
            payload = {"data": pkgs, "sizes": sizes, "counts": counts_all,
                       "m": m}
            nbytes = n_pkgs * ct_bytes + M * 8
            self.stats.n_packages += n_pkgs
        else:
            payload = {"data": flat_all, "sizes": None, "counts": counts_all,
                       "m": m}
            nbytes = M * n_slots * ct_bytes + M * 8
            self.stats.n_packages += M * n_slots
        self._reply(wire.SPLIT_INFOS, payload, nbytes)

    def on_chosen_sid(self, msg: dict) -> None:
        """The guest committed to one of this host's shuffled candidates:
        resolve it against the private permutation, record the (fid, bid)
        in the host-private table, and answer with the go-left bitmask
        over the node's instance space."""
        nid, sid = int(msg["nid"]), int(msg["sid"])
        rows = np.asarray(msg["rows"])
        real_sid = int(self.perms[nid][sid])
        fid, bid = decode_sid(real_sid, self.params.n_bins)
        self.table[nid] = (fid, bid)
        if self.table_sinks is not None:
            # round-forest gids demux into per-member tables with LOCAL
            # nids, so the serving export sees one table per member tree
            m, loc = divmod(nid, GID_STRIDE)
            self.table_sinks.setdefault(m, {})[loc] = (fid, bid)
        go_left = self.data.bins[rows, fid] <= bid
        self._reply(wire.ASSIGN_MASK, go_left, (len(go_left) + 7) // 8)


@dataclasses.dataclass
class TreeContext:
    params: object               # SBTParams (see boosting.py)
    cipher: object
    codec: object
    channel: Channel
    stats: Stats
    guest_data: BinnedData
    g: np.ndarray                # (n,) or (n, l), GOSS-weighted
    h: np.ndarray
    sel_rows: np.ndarray         # GOSS-selected row ids (into full set)
    hosts: list = dataclasses.field(default_factory=list)
    tree_idx: int = 0            # global tree counter (host shuffle seeds)
    forest_k: int = 1            # round-forest width sharing ONE enc_gh
    enc_shipped: bool = False    # enc_gh already broadcast (pipelined pump
                                 # ran before the grower, DESIGN.md §12)


def _crypto_mesh(params, cipher):
    """The (data, model) mesh when the limb crypto endpoints shard, else
    None (single device, or the python-int Paillier oracle)."""
    mesh = getattr(params, "mesh", None)
    if cipher.backend == "limb" and mesh is not None \
            and mesh.devices.size > 1:
        return mesh
    return None


def _encrypt_all(ctx: TreeContext, g_sel: np.ndarray,
                 h_sel: np.ndarray) -> None:
    """Guest packs + encrypts g/h of selected rows, broadcasts to hosts.

    Limb-backend ciphertexts are *born* at histogram width with their
    at-rest sharding (rule-table entries ``enc_plain`` / ``gh_cts``,
    DESIGN.md §8): the plaintext batch is placed once (padded to the
    data-axis extent), per-shard Pallas kernels encrypt with no collective,
    and :class:`CipherFrontier` adopts the buffers as-is — zero
    host->device re-placements after encryption.  The wire-byte ledger
    keeps protocol-fidelity counts via ``ct_wire_bytes`` regardless of the
    in-memory limb layout.
    """
    p = ctx.params
    blk = _stream_block(p, ctx.cipher, len(g_sel))
    if blk:
        _encrypt_all_chunked(ctx, g_sel, h_sel, blk)
        return
    tr = ctx.channel.tracer
    with tr.timed("encrypt", tree=int(ctx.tree_idx),
                  rows=len(g_sel)) as enc:
        with tr.span("encode", rows=len(g_sel), **ctx.codec.span_attrs):
            plain = ctx.codec.encode_plain(g_sel, h_sel)
        n, s, Lp = plain.shape
        if ctx.cipher.backend == "limb":
            import jax
            import jax.numpy as jnp
            from ..kernels.modmul import encrypt_batch
            width = ctx.cipher.hist_width
            mesh = _crypto_mesh(p, ctx.cipher)
            if mesh is not None:
                from ..parallel.sharding import data_pad, gbdt_sharding
                pad = data_pad(mesh, n)
                if pad:   # pad rows encrypt 0 -> 0 and never receive a slot
                    plain = np.concatenate(
                        [plain, np.zeros((pad, s, Lp), plain.dtype)], axis=0)
                plain_dev = jax.device_put(jnp.asarray(plain, jnp.int32),
                                           gbdt_sharding(mesh, "enc_plain"))
                if ctx.cipher.name == "affine" and p.use_pallas:
                    cts = encrypt_batch(ctx.cipher, plain_dev, mesh=mesh,
                                        out_width=width)
                else:
                    cts = limbs.pad_limbs(
                        ctx.cipher.encrypt_limbs(plain_dev), width)
            elif ctx.cipher.name == "affine" and p.use_pallas:
                cts = encrypt_batch(ctx.cipher, plain.reshape(n * s, Lp),
                                    out_width=width).reshape(n, s, width)
            else:
                cts = limbs.pad_limbs(
                    ctx.cipher.encrypt_limbs(jnp.asarray(plain)), width)
            jax.block_until_ready(cts)
        else:
            ints = limbs.to_pyints(plain.reshape(n * s, Lp))
            cts = ctx.cipher.encrypt_ints(ints).reshape(n, s)
    ctx.stats.n_encrypt += n * s
    ctx.stats.encrypt_seconds += enc.seconds
    nbytes = n * s * ct_wire_bytes(ctx.cipher) + n * 4   # + selected row ids
    codec_view = {"n_slots": int(ctx.codec.n_slots),
                  "compressible": bool(ctx.codec.compressible),
                  "eta_s": int(getattr(ctx.codec, "eta_s", 0)),
                  "b_slot": int(getattr(ctx.codec, "b_slot", 0))}
    payload = {"tree": int(ctx.tree_idx), "seed": int(p.seed),
               "forest": int(ctx.forest_k), "sel_rows": ctx.sel_rows,
               "codec": codec_view, "cts": cts}
    for host in ctx.hosts:
        host.bind(ctx.params, ctx.cipher, ctx.channel, ctx.stats)
        ctx.channel.send("guest", f"host{host.hid}", wire.ENC_GH, payload,
                         nbytes)
        host.deliver(wire.ENC_GH, payload)
    ctx.enc_shipped = True


def _stream_block(params, cipher, n: int) -> int:
    """Row-block size for the out-of-core path, or 0 for monolithic.

    Streaming engages only when a positive ``row_block`` is set, the batch
    actually exceeds it, and the cipher is limb-backed (the python-int
    Paillier oracle keeps the small-data monolithic path)."""
    rb = int(getattr(params, "row_block", 0) or 0)
    if rb > 0 and n > rb and cipher.backend == "limb":
        return rb
    return 0


def _encrypt_all_chunked(ctx: TreeContext, g_sel: np.ndarray,
                         h_sel: np.ndarray, block: int) -> None:
    """Chunked encrypt->ship (DESIGN.md §13): one row block at a time.

    Each block is encoded, encrypted on the single-device limb path, cast
    to its canonical radix-2^8 uint8 limbs and broadcast under the same
    ``enc_gh`` tag with framing fields (``blk``/``n_blocks``/``n_rows``/
    ``row_block`` plus the block's slice of ``sel_rows``).  Encryption is
    row-wise deterministic, so the concatenation of block ciphertexts is
    bit-identical to the monolithic batch; per-block wire bytes sum to the
    monolithic ledger total.  No party ever holds the full ciphertext
    batch: the guest frees each block after the ship and hosts assemble
    into a host-compact :class:`CtsBlocks`."""
    import jax
    import jax.numpy as jnp

    from ..kernels.modmul import encrypt_batch
    p = ctx.params
    n = len(g_sel)
    n_blocks = -(-n // block)
    Ln = ctx.cipher.Ln
    wire_ct = ct_wire_bytes(ctx.cipher)
    codec_view = {"n_slots": int(ctx.codec.n_slots),
                  "compressible": bool(ctx.codec.compressible),
                  "eta_s": int(getattr(ctx.codec, "eta_s", 0)),
                  "b_slot": int(getattr(ctx.codec, "b_slot", 0))}
    for host in ctx.hosts:
        host.bind(ctx.params, ctx.cipher, ctx.channel, ctx.stats)
    sel_rows = np.asarray(ctx.sel_rows)
    tr = ctx.channel.tracer
    for b in range(n_blocks):
        lo, hi = b * block, min((b + 1) * block, n)
        with tr.timed("encrypt_block", tree=int(ctx.tree_idx), blk=int(b),
                      rows=hi - lo) as enc:
            with tr.span("encode", rows=hi - lo, **ctx.codec.span_attrs):
                plain = ctx.codec.encode_plain(g_sel[lo:hi], h_sel[lo:hi])
            r, s, Lp = plain.shape
            if ctx.cipher.name == "affine" and p.use_pallas:
                cts = encrypt_batch(ctx.cipher, plain.reshape(r * s, Lp),
                                    out_width=Ln).reshape(r, s, Ln)
            else:
                cts = limbs.pad_limbs(
                    ctx.cipher.encrypt_limbs(jnp.asarray(plain)), Ln)
            cts_u8 = np.asarray(jax.device_get(cts)).astype(np.uint8)
        ctx.stats.n_encrypt += r * s
        ctx.stats.encrypt_seconds += enc.seconds
        ctx.stats.peak_block_bytes = max(
            ctx.stats.peak_block_bytes, int(cts_u8.nbytes) + r * 8)
        payload = {"tree": int(ctx.tree_idx), "seed": int(p.seed),
                   "forest": int(ctx.forest_k), "codec": codec_view,
                   "blk": b, "n_blocks": n_blocks, "n_rows": n,
                   "row_block": int(block),
                   "sel_rows": sel_rows[lo:hi], "cts": cts_u8}
        nbytes = r * s * wire_ct + r * 4
        for host in ctx.hosts:
            ctx.channel.send("guest", f"host{host.hid}", wire.ENC_GH, payload,
                             nbytes)
            host.deliver(wire.ENC_GH, payload)
    ctx.enc_shipped = True


class _EncryptPump:
    """Background encrypt-and-ship of one tree's ``enc_gh`` (DESIGN.md §12).

    Pipelined mode runs :func:`_encrypt_all` on a worker thread so the
    guest's plaintext work (layer-0 histogram candidates, or the previous
    round's remaining layers in the boosting driver's cross-round prefetch)
    overlaps the encrypt + broadcast.  The payload is byte-identical to the
    synchronous call — only wall-clock ordering changes — so pipelined runs
    stay bit-identical to sequential ones.

    ``join`` settles the overlap accounting: the encrypt wall time that
    elapsed before the joiner arrived was *hidden* behind useful work and
    accrues to ``Stats.prefetch_seconds`` (a subset of ``encrypt_seconds``,
    which :func:`_encrypt_all` still tallies in full); the per-tree hidden
    fraction lands in ``Stats.wire_overlap``.
    """

    def __init__(self, ctx: TreeContext, g_sel: np.ndarray,
                 h_sel: np.ndarray):
        import threading
        self.ctx = ctx
        self._err: BaseException | None = None
        self._done_t: float | None = None
        self._t0 = time.perf_counter()
        self._thread = threading.Thread(
            target=self._run, args=(g_sel, h_sel), daemon=True)
        self._thread.start()

    def _run(self, g_sel, h_sel) -> None:
        try:
            _encrypt_all(self.ctx, g_sel, h_sel)
        except BaseException as e:          # surfaced at join()
            self._err = e
        finally:
            self._done_t = time.perf_counter()

    def join(self) -> None:
        t_join = time.perf_counter()
        self._thread.join()
        if self._err is not None:
            raise self._err
        enc = max(self._done_t - self._t0, 0.0)
        hidden = max(0.0, min(self._done_t, t_join) - self._t0)
        stats = self.ctx.stats
        stats.prefetch_seconds += hidden
        stats.wire_overlap.append(hidden / enc if enc > 0 else 0.0)


def _resolve_modes(splittable: list, hist_mode: dict, cache,
                   subtraction_on: bool) -> tuple[list, list]:
    """Partition a layer's splittable nodes into direct / subtract batches.

    ``cache`` is any container answering ``nid in cache`` for cached parent
    histograms (a ``CipherFrontier`` / ``GuestFrontier``).  A node keeps its
    scheduled "subtract" mode only when its parent's histogram is cached AND
    its (direct-mode) sibling is being computed this layer -- otherwise it
    falls back to direct, exactly like the per-node path did when a sibling
    exited early as a leaf.  ``splittable`` must be ordered direct-first so
    siblings are classified before their subtract partners."""
    direct: list = []
    subtract: list = []
    direct_set: set = set()
    for nid in splittable:
        mode, par, sib = hist_mode[nid] if subtraction_on \
            else ("direct", -1, -1)
        if mode == "subtract" and par in cache and sib in direct_set:
            subtract.append((nid, par, sib))
        else:
            direct.append(nid)
            direct_set.add(nid)
    return direct, subtract


def _layer_candidates(ctx: TreeContext, depth: int, plan: dict | None,
                      hosts: list, splittable: list,
                      guest: Callable[[], dict]) -> tuple:
    """One layer's candidate phase: every participating host gets the
    layer ``plan`` (in-process, delivering it runs the host's
    ``host_layer`` work, which leaves the cipher pipeline in flight on the
    device), the guest computes its plaintext candidates (``guest()``)
    meanwhile, then each host's ``split_infos`` reply is decrypted and
    decoded.  The two sides are independent until ``find_best_split``
    (DESIGN.md §8).  Returns ``(guest_cands, host_cands)``."""
    def sync_plan():
        for h in hosts:
            ctx.channel.send("guest", f"host{h.hid}", wire.ASSIGN_SYNC,
                             plan, plan["node_of"].size * 4)
            h.deliver(wire.ASSIGN_SYNC, plan)

    if not splittable:      # the plan still carries the hosts' eviction
        sync_plan()
        return {}, {}
    tr = ctx.channel.tracer
    tkw = dict(tree=int(ctx.tree_idx), depth=int(depth))
    host_cands: dict = {}
    with tr.timed("layer", nodes=len(splittable), **tkw) as layer:
        sync_plan()
        with tr.timed("guest_hist", **tkw) as gh:
            guest_cands = guest()
        with tr.timed("decrypt_wait", **tkw) as wait:
            for h in hosts:
                ctx.stats.n_split_roundtrips += 1
                host_cands[h.hid] = _host_layer_finish(ctx, h, splittable)
    if hosts:
        ctx.stats.guest_hist_seconds += gh.seconds
        ctx.stats.host_wait_seconds += wait.seconds
        # overlap only exists for async-dispatch backends: the Paillier
        # oracle completes synchronously inside the plan's delivery, so
        # nothing is in flight while the guest works
        if guest_cands and ctx.cipher.backend == "limb":
            ctx.stats.layer_overlap.append(
                gh.seconds / layer.seconds if layer.dur_ns > 0 else 0.0)
    return guest_cands, host_cands


def _host_layer_finish(ctx: TreeContext, host, splittable: list) -> dict:
    """Guest side of the layer batch: ONE batched decrypt + decode
    (Algorithm 6) of the candidate stack a host answered ``assign_sync``
    with (``HostRuntime.on_assign_sync``).  In-process the stack is still
    device-resident and waiting for its plaintexts synchronizes the whole
    in-flight cipher pipeline; over the transport it arrives as a decoded
    limb tensor.  A codec with a ``decode_device`` (MO) decodes the
    plaintexts where they are; the others copy them to numpy in the
    decrypt.  Returns {nid: SplitCandidates}."""
    limb = ctx.cipher.backend == "limb"
    codec = ctx.codec
    on_device = limb and codec.decode_device is not None
    n_slots = codec.n_slots
    tr = ctx.channel.tracer
    with tr.span("decrypt", tree=int(ctx.tree_idx),
                 nodes=len(splittable)) as dcr:
        pending = host.collect(wire.SPLIT_INFOS)
        data, sizes, cl = pending["data"], pending["sizes"], pending["counts"]
        m = int(pending["m"])
        M = m * len(splittable)
        if sizes is None:
            # keep a limb candidate stack on device into the decrypt
            data = data.reshape((M * n_slots, -1) if limb else M * n_slots)
        plain = _decrypt(ctx, data, on_device)
        if tr.enabled:
            dcr.attrs["cts"] = len(plain)
    ctx.stats.n_decrypt += len(plain)
    with tr.span("decode", tree=int(ctx.tree_idx), ints=len(plain),
                 **codec.span_attrs) as dec:
        gh, path = None, "device"
        if on_device:
            gh = codec.decode_device(plain, cl)
            if gh is None:      # a field too wide for the bit reader
                plain = np.asarray(plain)
        if gh is None and limb:
            gh, path = codec.decode_limbs(plain, cl, sizes), "words"
        if gh is None:      # the Paillier oracle, or a field too wide
            gh, path = _decode_pyints(codec, plain, cl, sizes, limb), "pyints"
        if path == "device":
            ctx.stats.n_decode_device += M
        elif path == "words":
            ctx.stats.n_decode_words += M
        else:
            ctx.stats.n_decode_pyints += M
        if tr.enabled:
            dec.attrs["path"] = path
        g_l, h_l = gh
    out = {}
    for k, nid in enumerate(splittable):
        sl = slice(k * m, (k + 1) * m)
        out[nid] = SplitCandidates(party=host.hid, sid=np.arange(m),
                                   g_l=g_l[sl], h_l=h_l[sl], cnt_l=cl[sl])
    return out


def _decode_pyints(codec, plain, counts, sizes, limb: bool):
    """The candidates' g/h sums through python ints: the Paillier oracle's
    path, and the reference ``codec.decode_limbs`` matches bit for bit.
    ``plain`` is limbs on the limb backends (short package groups padded
    to ``eta_s`` slots there), python ints on the oracle."""
    ints = limbs.to_pyints(plain) if limb else plain
    if sizes is not None:
        ints = compress_mod.decompress_ints(
            ints, sizes, codec.eta_s, codec.b_slot, padded=limb)
    rows = np.asarray(ints, dtype=object).reshape(len(counts), codec.n_slots)
    return codec.decode(rows, counts)


def _decrypt(ctx: TreeContext, cts, on_device: bool):
    """Plaintexts of a candidate stack: limbs on the limb backends, numpy
    or, ``on_device``, left on the device once ready (either waits for the
    device work still in flight); python ints on the Paillier oracle."""
    if ctx.cipher.backend != "limb":
        return ctx.cipher.decrypt_to_ints(cts)
    import jax.numpy as jnp

    def ready(plain):
        return plain.block_until_ready() if on_device else np.asarray(plain)
    x = jnp.asarray(cts)
    if not (ctx.cipher.name == "affine" and ctx.params.use_pallas):
        return ready(ctx.cipher.decrypt_limbs(x))
    from ..kernels.modmul import decrypt_batch
    mesh = _crypto_mesh(ctx.params, ctx.cipher)
    n = x.shape[0]
    # shard only when every shard gets at least one full-size kernel
    # row block: cipher-compressed package batches are small by
    # design (that is the point of compression) and would pay a
    # shard_map compile per pow2 bucket for sub-millisecond matmuls;
    # large stacks (no-compress / MO / deep frontiers) shard for real
    from ..kernels.modmul.modmul import BLOCK_N
    dd = dict(mesh.shape).get("data", 1) if mesh is not None else 1
    if mesh is not None and n >= BLOCK_N * dd:
        import jax

        from ..parallel.sharding import data_pad, gbdt_sharding
        # the candidate stack is still device-resident: pad the
        # candidate axis to the next power of two (the per-layer
        # candidate count varies with the frontier, and the padded
        # extent is a static shape — pow2 bucketing caps distinct
        # compilations at O(log max_M), mirroring the node padding
        # of the layer dispatch), then shard per the rule table and
        # decrypt per shard with no collective
        bucket = 1 << max(n - 1, 0).bit_length()
        bucket += data_pad(mesh, bucket)
        if bucket > n:
            x = jnp.pad(x, [(0, bucket - n)] + [(0, 0)] * (x.ndim - 1))
        x = jax.device_put(
            x, gbdt_sharding(mesh, "split_infos", ndim=x.ndim))
        plain = decrypt_batch(ctx.cipher, x, mesh=mesh)
        return plain[:n].block_until_ready() if on_device \
            else np.asarray(plain)[:n]
    return ready(limbs.by_row_blocks(
        lambda a: decrypt_batch(ctx.cipher, a), x))


def _guest_layer_candidates(ctx: TreeContext, guest_frontier: GuestFrontier,
                            splittable: list, rows_sel: dict,
                            hist_mode: dict) -> dict:
    """Guest-side plaintext mirror of the layer batch: one composite
    ``np.add.at`` pass for all direct nodes, subtraction for the rest."""
    direct, subtract = _resolve_modes(splittable, hist_mode, guest_frontier,
                                      ctx.params.histogram_subtraction)
    node_rows = {nid: ctx.sel_rows[rows_sel[nid]] for nid in splittable}
    hists = guest_frontier.layer_histograms(node_rows, direct, subtract)
    out = {}
    for nid in splittable:
        Gc, Hc, Cc = guest_frontier.cumsum(hists[nid])
        out[nid] = candidates_from_cumsum(Gc, Hc, Cc, party=GUEST)
    return out


# ---------------------------------------------------------------------------
# the grower
# ---------------------------------------------------------------------------

def grow_tree(ctx: TreeContext,
              feature_parties: Callable[[int], tuple] | None = None
              ) -> tuple:
    """Grow one federated tree.  ``feature_parties(depth) -> (use_guest,
    host_ids)`` schedules which parties contribute split candidates at each
    depth (mix / layered modes); default: everyone, every depth.

    Returns ``(tree, leaf_rows)``: the model and the training row -> leaf
    assignment.  ``leaf_rows`` is train-side state consumed once by the
    boosting driver's score update — it is deliberately NOT attached to the
    :class:`FederatedTree`, so a model held for serving (or exported via
    ``serving/export.py``) carries no row-level training residue."""
    with ctx.channel.tracer.span("tree", tree=int(ctx.tree_idx),
                                 rows=len(ctx.sel_rows)):
        return _grow_tree(ctx, feature_parties)


def _grow_tree(ctx: TreeContext,
               feature_parties: Callable[[int], tuple] | None) -> tuple:
    p = ctx.params
    if feature_parties is None:
        feature_parties = lambda d: (True, [h.hid for h in ctx.hosts])

    # hoisted once per tree: g/h restricted to the GOSS selection
    g_sel = ctx.g[ctx.sel_rows]
    h_sel = ctx.h[ctx.sel_rows]

    pump = None
    any_host = any(feature_parties(d)[1] for d in range(p.max_depth))
    if any_host and not ctx.enc_shipped:
        if getattr(p, "pipeline", False):
            # pipelined: encrypt + broadcast on a worker thread; the guest's
            # layer-0 plaintext candidates run concurrently and the pump is
            # joined right before the first assign_sync (DESIGN.md §12)
            pump = _EncryptPump(ctx, g_sel, h_sel)
        else:
            _encrypt_all(ctx, g_sel, h_sel)

    plain_engine = PlainHistogram(p.n_bins, sparse=p.sparse,
                                 row_block=getattr(p, "row_block", 0))
    guest_frontier = GuestFrontier(plain_engine, ctx.guest_data, ctx.g, ctx.h)

    n_all = ctx.guest_data.n_instances
    nodes = [Node(nid=0, depth=0, n_rows=n_all)]
    rows_all = {0: np.arange(n_all)}
    rows_sel = {0: np.arange(len(ctx.sel_rows))}   # positions into sel arrays
    hist_mode = {0: ("direct", -1, -1)}

    frontier = [0]
    for depth in range(p.max_depth):
        use_guest, host_ids = feature_parties(depth)
        active_hosts = [h for h in ctx.hosts if h.hid in host_ids]
        next_frontier = []
        # order: direct nodes before subtract nodes (siblings first)
        ordered = [n for n in frontier if hist_mode[n][0] == "direct"] + \
                  [n for n in frontier if hist_mode[n][0] == "subtract"]
        # sync one assignment vector per layer to hosts that participate
        if active_hosts:
            node_of = np.full(len(ctx.sel_rows), -1, np.int32)
            for nid in frontier:
                node_of[rows_sel[nid]] = nid

        # triage: nodes too small to split become leaves immediately; the
        # rest form this layer's batch
        splittable = []
        for nid in ordered:
            rs = rows_sel[nid]
            if len(rs) < 2 * p.min_leaf or len(rs) == 0:
                nodes[nid].weight = leaf_weight(
                    g_sel[rs].sum(axis=0), h_sel[rs].sum(axis=0),
                    p.lam, p.learning_rate)
            else:
                splittable.append(nid)

        # one candidate batch per party for the whole layer.  The layer
        # plan (assignment vector + splittable batch + subtraction
        # schedule) is ONE serializable assign_sync message per host; each
        # host answers with ONE split_infos message.  In-process the
        # delivery in _layer_candidates runs the host pipeline as jax async
        # dispatch; with remote hosts it is a no-op (the channel already
        # shipped the plan) and every host process computes concurrently.
        # Either way the guest's plaintext numpy histograms run while the
        # host cipher work is in flight, and only then does the guest
        # block on the batched decrypt — the two sides are independent
        # until find_best_split (DESIGN.md §8).
        # pipelined: the guest's plaintext layer candidates are the useful
        # work that hides the pump's encrypt + broadcast; compute them
        # BEFORE joining, then join so the assign_sync below never races
        # ahead of the enc_gh it depends on
        pre_cands = None
        if pump is not None:
            if splittable and use_guest and ctx.guest_data.n_features > 0:
                pre_cands = _guest_layer_candidates(
                    ctx, guest_frontier, splittable, rows_sel, hist_mode)
            pump.join()
            pump = None

        plan = None
        if active_hosts:
            plan = {"tree": int(ctx.tree_idx),
                    "node_of": node_of,
                    "splittable": list(splittable),
                    "modes": [(nid,) + tuple(hist_mode[nid])
                              for nid in splittable]}

        def guest():
            if pre_cands is not None:
                return pre_cands
            if use_guest and ctx.guest_data.n_features > 0:
                return _guest_layer_candidates(
                    ctx, guest_frontier, splittable, rows_sel, hist_mode)
            return {}

        guest_cands, host_cands = _layer_candidates(
            ctx, depth, plan, active_hosts, splittable, guest)

        with ctx.channel.tracer.span("split", tree=int(ctx.tree_idx),
                                     depth=int(depth),
                                     nodes=len(splittable)):
            for nid in splittable:
                node = nodes[nid]
                rs = rows_sel[nid]
                G_tot = g_sel[rs].sum(axis=0)
                H_tot = h_sel[rs].sum(axis=0)

                cands = []
                if nid in guest_cands:
                    cands.append(guest_cands[nid])
                for h in active_hosts:
                    cands.append(host_cands[h.hid][nid])

                best = find_best_split(cands, G_tot, H_tot, len(rs), p.lam,
                                       p.min_leaf, p.min_gain)
                if best is None:
                    node.weight = leaf_weight(G_tot, H_tot, p.lam,
                                              p.learning_rate)
                    continue

                # resolve the split owner + instance assignment
                ra = rows_all[nid]
                fsel = ctx.sel_rows[rs]         # full ids of selected rows
                if best.party == GUEST:
                    fid, bid = decode_sid(best.sid, p.n_bins)
                    go_left = ctx.guest_data.bins[ra, fid] <= bid
                    go_left_sel = ctx.guest_data.bins[fsel, fid] <= bid
                    node.party, node.fid, node.bid = GUEST, fid, bid
                else:
                    # the chosen split id travels WITH the node's instance
                    # space (the host resolves its private (fid, bid) and
                    # answers one go-left bitmask over those rows); the
                    # selected-row mask is derived guest-side — fsel is
                    # always a subset of the ascending ra, so no second
                    # message
                    host = next(h for h in ctx.hosts if h.hid == best.party)
                    msg = {"nid": nid, "sid": best.sid, "rows": ra}
                    ctx.channel.send("guest", f"host{host.hid}",
                                     wire.CHOSEN_SID, msg, 8 + 4 * len(ra))
                    host.deliver(wire.CHOSEN_SID, msg)
                    go_left = np.asarray(host.collect(wire.ASSIGN_MASK), bool)
                    go_left_sel = go_left[np.searchsorted(ra, fsel)]
                    node.party, node.sid = host.hid, best.sid
                node.gain = best.gain

                lid, rid = len(nodes), len(nodes) + 1
                node.left, node.right = lid, rid
                rows_all[lid], rows_all[rid] = ra[go_left], ra[~go_left]
                rows_sel[lid] = rs[go_left_sel]
                rows_sel[rid] = rs[~go_left_sel]
                nodes.append(Node(nid=lid, depth=depth + 1,
                                  n_rows=len(rows_all[lid])))
                nodes.append(Node(nid=rid, depth=depth + 1,
                                  n_rows=len(rows_all[rid])))
                # subtraction schedule: smaller child direct, sibling subtracts
                if len(rows_sel[lid]) <= len(rows_sel[rid]):
                    hist_mode[lid] = ("direct", -1, -1)
                    hist_mode[rid] = ("subtract", nid, lid)
                else:
                    hist_mode[rid] = ("direct", -1, -1)
                    hist_mode[lid] = ("subtract", nid, rid)
                next_frontier += [lid, rid]
            # free cached histograms: keep ONLY the parents the next layer's
            # subtract-mode nodes will read.  Evicting just the used parents
            # leaked every histogram cached for a node that became a leaf
            # (triage, best=None, or max depth) — device memory grew with
            # each dead branch for the tree's remainder.
            keep = ({hist_mode[c][1] for c in next_frontier
                     if hist_mode[c][0] == "subtract"}
                    if p.histogram_subtraction else set())
            sizes = [guest_frontier.evict_except(keep)]
            for h in ctx.hosts:
                # remote handles hold no frontier: their PartyProcess evicts
                # against the same schedule when the next assign_sync arrives
                if getattr(h, "frontier", None) is not None:
                    sizes.append(h.frontier.evict_except(keep))
            ctx.stats.peak_hist_cache = max(ctx.stats.peak_hist_cache,
                                            max(sizes))
        ctx.stats.peak_frontier = max(ctx.stats.peak_frontier, len(frontier))
        frontier = next_frontier

    if pump is not None:        # degenerate: no layer ever joined it
        pump.join()

    # finalize leaves at max depth
    for node in nodes:
        if node.left == -1 and node.weight is None:
            rs = rows_sel[node.nid]
            node.weight = leaf_weight(g_sel[rs].sum(axis=0),
                                      h_sel[rs].sum(axis=0),
                                      p.lam, p.learning_rate)

    # leaf row assignment for the score update (returned alongside, never
    # retained on the model: the tree must stay free of row-level state)
    leaf_rows = {n.nid: rows_all[n.nid] for n in nodes if n.left == -1}
    tree = FederatedTree(nodes=nodes,
                         host_tables=[h.table for h in ctx.hosts])
    return tree, leaf_rows


def grow_forest(ctx: TreeContext, bags: list,
                feature_parties: Callable[[int], tuple] | None = None
                ) -> list:
    """Grow one round-forest: ``k = len(bags)`` bagged member trees that
    share ONE ``enc_gh`` broadcast (FedGBF-style round bagging, DESIGN.md
    §12).  ``bags[m]`` holds member m's row subset as positions into
    ``ctx.sel_rows``; bags restrict only which rows *contribute* g/h to
    split finding — every training row still routes through every member
    for the score update, so ``rows_all`` starts at the full set per member.

    All members grow in lockstep, layer by layer.  Each layer is still ONE
    ``assign_sync`` -> ONE ``split_infos`` -> ONE batched decrypt per host:
    the assignment matrix gains a member column, the histogram launch
    batches over (member, node) via the forest kernel, and node ids on the
    wire are globals ``gid = member * GID_STRIDE + local_nid`` (host dicts
    key on the opaque gid; the guest demuxes tables per member on
    finalize).  Amortization is the point: k trees cost one encrypt
    round-trip and O(depth) — not O(k * depth) — protocol round trips.

    Returns ``[(tree, leaf_rows), ...]`` per member, the same pair
    :func:`grow_tree` returns.
    """
    p = ctx.params
    k = len(bags)
    if feature_parties is None:
        feature_parties = lambda d: (True, [h.hid for h in ctx.hosts])

    g_sel = ctx.g[ctx.sel_rows]
    h_sel = ctx.h[ctx.sel_rows]

    pump = None
    any_host = any(feature_parties(d)[1] for d in range(p.max_depth))
    if any_host and not ctx.enc_shipped:
        if getattr(p, "pipeline", False):
            pump = _EncryptPump(ctx, g_sel, h_sel)
        else:
            _encrypt_all(ctx, g_sel, h_sel)

    plain_engine = PlainHistogram(p.n_bins, sparse=p.sparse,
                                 row_block=getattr(p, "row_block", 0))
    guest_frontier = GuestFrontier(plain_engine, ctx.guest_data, ctx.g, ctx.h)

    n_all = ctx.guest_data.n_instances
    # per-member node lists carry LOCAL nids; all protocol/guest dict state
    # (rows, modes, caches, host tables) keys on the global gid
    nodes = [[Node(nid=0, depth=0, n_rows=n_all)] for _ in range(k)]
    rows_all: dict = {}
    rows_sel: dict = {}
    hist_mode: dict = {}
    frontier: list = []
    for m in range(k):
        gid0 = m * GID_STRIDE
        rows_all[gid0] = np.arange(n_all)
        rows_sel[gid0] = np.asarray(bags[m])
        hist_mode[gid0] = ("direct", -1, -1)
        frontier.append(gid0)

    for depth in range(p.max_depth):
        use_guest, host_ids = feature_parties(depth)
        active_hosts = [h for h in ctx.hosts if h.hid in host_ids]
        next_frontier = []
        ordered = [n for n in frontier if hist_mode[n][0] == "direct"] + \
                  [n for n in frontier if hist_mode[n][0] == "subtract"]
        if active_hosts:
            # one assignment column per member: a row sits in at most one
            # frontier node per member tree
            node_of = np.full((len(ctx.sel_rows), k), -1, np.int32)
            for gid in frontier:
                node_of[rows_sel[gid], gid // GID_STRIDE] = gid

        splittable = []
        for gid in ordered:
            rs = rows_sel[gid]
            node = nodes[gid // GID_STRIDE][gid % GID_STRIDE]
            if len(rs) < 2 * p.min_leaf or len(rs) == 0:
                node.weight = leaf_weight(
                    g_sel[rs].sum(axis=0), h_sel[rs].sum(axis=0),
                    p.lam, p.learning_rate)
            else:
                splittable.append(gid)

        pre_cands = None
        if pump is not None:
            if splittable and use_guest and ctx.guest_data.n_features > 0:
                pre_cands = _guest_layer_candidates(
                    ctx, guest_frontier, splittable, rows_sel, hist_mode)
            pump.join()
            pump = None

        plan = None
        if active_hosts:
            plan = {"tree": int(ctx.tree_idx), "forest": k,
                    "node_of": node_of,
                    "splittable": list(splittable),
                    "modes": [(gid,) + tuple(hist_mode[gid])
                              for gid in splittable]}

        def guest():
            if pre_cands is not None:
                return pre_cands
            if use_guest and ctx.guest_data.n_features > 0:
                return _guest_layer_candidates(
                    ctx, guest_frontier, splittable, rows_sel, hist_mode)
            return {}

        guest_cands, host_cands = _layer_candidates(
            ctx, depth, plan, active_hosts, splittable, guest)

        with ctx.channel.tracer.span("split", tree=int(ctx.tree_idx),
                                     depth=int(depth),
                                     nodes=len(splittable)):
            for gid in splittable:
                m = gid // GID_STRIDE
                node = nodes[m][gid % GID_STRIDE]
                rs = rows_sel[gid]
                G_tot = g_sel[rs].sum(axis=0)
                H_tot = h_sel[rs].sum(axis=0)

                cands = []
                if gid in guest_cands:
                    cands.append(guest_cands[gid])
                for h in active_hosts:
                    cands.append(host_cands[h.hid][gid])

                best = find_best_split(cands, G_tot, H_tot, len(rs), p.lam,
                                       p.min_leaf, p.min_gain)
                if best is None:
                    node.weight = leaf_weight(G_tot, H_tot, p.lam,
                                              p.learning_rate)
                    continue

                ra = rows_all[gid]
                fsel = ctx.sel_rows[rs]
                if best.party == GUEST:
                    fid, bid = decode_sid(best.sid, p.n_bins)
                    go_left = ctx.guest_data.bins[ra, fid] <= bid
                    go_left_sel = ctx.guest_data.bins[fsel, fid] <= bid
                    node.party, node.fid, node.bid = GUEST, fid, bid
                else:
                    host = next(h for h in ctx.hosts if h.hid == best.party)
                    msg = {"nid": gid, "sid": best.sid, "rows": ra}
                    ctx.channel.send("guest", f"host{host.hid}",
                                     wire.CHOSEN_SID, msg, 8 + 4 * len(ra))
                    host.deliver(wire.CHOSEN_SID, msg)
                    go_left = np.asarray(host.collect(wire.ASSIGN_MASK), bool)
                    go_left_sel = go_left[np.searchsorted(ra, fsel)]
                    node.party, node.sid = host.hid, best.sid
                node.gain = best.gain

                lid, rid = len(nodes[m]), len(nodes[m]) + 1
                gl, gr = m * GID_STRIDE + lid, m * GID_STRIDE + rid
                node.left, node.right = lid, rid
                rows_all[gl], rows_all[gr] = ra[go_left], ra[~go_left]
                rows_sel[gl], rows_sel[gr] = rs[go_left_sel], rs[~go_left_sel]
                nodes[m].append(Node(nid=lid, depth=depth + 1,
                                     n_rows=len(rows_all[gl])))
                nodes[m].append(Node(nid=rid, depth=depth + 1,
                                     n_rows=len(rows_all[gr])))
                if len(rows_sel[gl]) <= len(rows_sel[gr]):
                    hist_mode[gl] = ("direct", -1, -1)
                    hist_mode[gr] = ("subtract", gid, gl)
                else:
                    hist_mode[gr] = ("direct", -1, -1)
                    hist_mode[gl] = ("subtract", gid, gr)
                next_frontier += [gl, gr]

            keep = ({hist_mode[c][1] for c in next_frontier
                     if hist_mode[c][0] == "subtract"}
                    if p.histogram_subtraction else set())
            sizes = [guest_frontier.evict_except(keep)]
            for h in ctx.hosts:
                if getattr(h, "frontier", None) is not None:
                    sizes.append(h.frontier.evict_except(keep))
            ctx.stats.peak_hist_cache = max(ctx.stats.peak_hist_cache,
                                            max(sizes))
        ctx.stats.peak_frontier = max(ctx.stats.peak_frontier, len(frontier))
        frontier = next_frontier

    if pump is not None:
        pump.join()

    # finalize: leaves at max depth, per-member host-table demux (gid ->
    # local nid; remote handles hold no table — their PartyProcess demuxes
    # via ``table_sinks`` into its own per-member export tables)
    tables_by_member = [[{} for _ in ctx.hosts] for _ in range(k)]
    for j, h in enumerate(ctx.hosts):
        for gid, fb in getattr(h, "table", {}).items():
            mm, loc = divmod(int(gid), GID_STRIDE)
            tables_by_member[mm][j][loc] = fb
    out = []
    for m in range(k):
        for node in nodes[m]:
            if node.left == -1 and node.weight is None:
                rs = rows_sel[m * GID_STRIDE + node.nid]
                node.weight = leaf_weight(g_sel[rs].sum(axis=0),
                                          h_sel[rs].sum(axis=0),
                                          p.lam, p.learning_rate)
        leaf_rows = {nd.nid: rows_all[m * GID_STRIDE + nd.nid]
                     for nd in nodes[m] if nd.left == -1}
        out.append((FederatedTree(nodes=nodes[m],
                                  host_tables=tables_by_member[m]),
                    leaf_rows))
    return out


def predict_tree(tree: FederatedTree, guest_bins: np.ndarray,
                 host_bins: list) -> np.ndarray:
    """Route binned instances through the tree (simulation: reads host
    tables directly; the real protocol does the same lookups host-side)."""
    n = guest_bins.shape[0]
    first = next(nd for nd in tree.nodes if nd.weight is not None)
    w0 = np.asarray(first.weight)
    out = np.zeros((n,) + w0.shape)
    node_of = np.zeros(n, np.int64)
    changed = True
    while changed:
        changed = False
        for nd in tree.nodes:
            if nd.left == -1:
                continue
            sel = node_of == nd.nid
            if not sel.any():
                continue
            if nd.party == GUEST:
                go_left = guest_bins[sel, nd.fid] <= nd.bid
            else:
                fid, bid = tree.host_tables[nd.party][nd.nid]
                go_left = host_bins[nd.party][sel, fid] <= bid
            ids = np.where(sel)[0]
            node_of[ids[go_left]] = nd.left
            node_of[ids[~go_left]] = nd.right
            changed = True
    for nd in tree.nodes:
        if nd.left == -1 and nd.weight is not None:
            out[node_of == nd.nid] = nd.weight
    return out
