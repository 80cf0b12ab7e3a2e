"""SecureBoost+ boosting driver (paper §3-§6) and the local baseline.

``VerticalBoosting`` orchestrates guest + hosts over a byte-counted channel:

  objective   "binary" (one tree/round), "multiclass" (one tree per class
              per round -- the paper's *default* multi-class setting), or
              "mo" (SecureBoost-MO: one multi-output tree per round)
  tree_mode   "default" | "mix" | "layered"  (paper §5.1-5.2)
  cipher      "plain" | "affine" | "paillier"
  packing / histogram_subtraction / compression / goss / sparse  -- ablations

``LocalGBDT`` is the plaintext single-party baseline (the XGBoost role in
the paper's tables): identical binning, gains, and leaf weights, so the
federated model with the plain cipher is bit-identical to it (tested).
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from . import encoding, mo_encoding
from ..data.pipeline import RowBlocks
from .binning import BinnedData, bin_features, bin_features_stream
from .goss import goss_sample
from .he import get_cipher
from .histogram import CipherHistogram
from .loss import LogLoss, SoftmaxLoss
from ..obs import trace as obs_trace
from ..obs.trace import NULL_TRACER, Tracer
from .party import Channel, Stats
from .tree import (GUEST, FederatedTree, HostRuntime, MOCodec, NoPackCodec,
                   PackedCodec, TreeContext, _EncryptPump, _encrypt_all,
                   grow_forest, grow_tree, predict_tree)


@dataclasses.dataclass
class SBTParams:
    n_trees: int = 10
    max_depth: int = 5
    learning_rate: float = 0.3
    lam: float = 1.0
    n_bins: int = 32
    min_leaf: int = 1
    min_gain: float = 1e-6
    objective: str = "binary"          # binary | multiclass | mo
    n_classes: int = 2
    cipher: str = "plain"              # plain | affine | paillier
    key_bits: int = 1024
    precision: int = encoding.DEFAULT_PRECISION
    goss: bool = False
    top_rate: float = 0.2
    other_rate: float = 0.1
    packing: bool = True
    histogram_subtraction: bool = True
    compression: bool = True
    sparse: bool = False
    tree_mode: str = "default"         # default | mix | layered
    guest_depth: int = 2               # layered mode
    host_depth: int = 3
    trees_per_party: int = 1           # mix mode
    use_pallas: bool = True
    pipeline: bool = False             # pipelined boosting (DESIGN.md §12):
                                       # encrypt+broadcast of the next
                                       # tree's enc_gh overlaps the current
                                       # tree's growth; bit-identical to
                                       # sequential for forest_size=1
    forest_size: int = 1               # round-forest width (FedGBF-style):
                                       # k bagged shallow trees per round
                                       # share ONE enc_gh round-trip;
                                       # binary objective only
    forest_subsample: float = 0.8      # per-member bag fraction of the
                                       # (GOSS-)selected rows
    seed: int = 0
    mesh: object = None                # optional (data, model) jax Mesh: the
                                       # frontier engine shards instances
                                       # over "data" and the layer histogram
                                       # node axis over "model" (DESIGN §5/§7)
    row_block: int = 0                 # out-of-core row-block size (§13):
                                       # > 0 streams every O(rows) training
                                       # stage (encrypt->ship, frontier
                                       # accumulation, guest histograms) in
                                       # blocks of this many rows whenever a
                                       # batch exceeds it; 0 keeps the
                                       # monolithic fast path.  Bit-identical
                                       # either way (limb backends only)
    trace: bool = False                # structured tracing (DESIGN.md §14):
                                       # record span/instant events into a
                                       # bounded per-party ring buffer.
                                       # Protocol- and model-neutral: only
                                       # observation, never control flow


def cipher_kwargs(params: SBTParams) -> dict:
    """Cipher-construction kwargs from run params — the SINGLE definition
    shared by the guest driver and the multi-host PartyProcess, so the
    two sides can never silently diverge on key parameters."""
    if params.cipher == "plain":
        return {"bits": max(params.key_bits, 256)}
    return {"key_bits": params.key_bits, "seed": params.seed}


class VerticalBoosting:
    def __init__(self, params: SBTParams):
        self.params = params
        self.trees: list[FederatedTree] = []
        self.tree_class: list[int] = []   # multiclass: class of each tree
        self.channel = Channel()
        self.stats = Stats()
        self.tracer = NULL_TRACER
        self.init_score = None
        self._loss = None
        self._predictor = None            # cached packed serving engine
        self._predictor_n_trees = -1
        # multi-host mode (runtime/transport.py): handles to host parties
        # living in their own OS processes.  When set, ``fit`` is called
        # with X_hosts=[] — host features never enter this process — and
        # every cross-party message flows through the transport channel.
        self.remote_hosts: list | None = None

    # ------------------------------------------------------------------
    # fit = begin_fit + boost_round per round + finish_fit.  The split
    # exists for the fault-tolerant runtime (runtime/transport.py): each
    # round is a resume boundary — ``boost_round`` is transactional
    # (state is only committed once the whole round succeeded), so a
    # faulted round can be replayed bit-identically from the boundary
    # after ``rollback_to_round``.
    def fit(self, X_guest: np.ndarray, y: np.ndarray,
            X_hosts: list[np.ndarray]):
        score = self.begin_fit(X_guest, y, X_hosts)
        for t in range(self.params.n_trees):
            score = self.boost_round(t, score)
        return self.finish_fit(score)

    def begin_fit(self, X_guest: np.ndarray, y: np.ndarray,
                  X_hosts: list[np.ndarray]) -> np.ndarray:
        """Reset model state, bin features, init the loss/cipher; returns
        the initial score vector (the round-0 boundary state)."""
        p = self.params
        # a refit is a fresh model: without these resets a second fit()
        # appended n_trees more trees whose (fid, bid) splits were decoded
        # against the NEW fit's binning thresholds — silently wrong
        # scores — and stats/ledger accumulated across fits
        if p.forest_size > 1 and p.objective != "binary":
            raise ValueError(
                "forest_size > 1 (round-forests) requires objective="
                "'binary': multiclass rounds already batch one tree per "
                "class and MO packs classes into slots")
        self.trees = []
        self.tree_class = []
        self.stats = Stats()
        self.channel.reset_accounting()
        # guest tracer: params.trace makes a fresh per-fit buffer; an
        # enabled process-default tracer (benchmark harness --trace) is
        # inherited so benches need no plumbing; else the null tracer
        # keeps every emission site one-bool-test cheap
        if p.trace:
            self.tracer = Tracer("guest")
        elif obs_trace.current().enabled:
            self.tracer = obs_trace.current()
        else:
            self.tracer = NULL_TRACER
        self.channel.tracer = self.tracer
        self._predictor = None            # stale after refit
        self._predictor_n_trees = -1
        self.guest_data = self._bin(X_guest)
        self.host_data = [self._bin(Xh) for Xh in X_hosts]
        y = np.asarray(y, np.float64)
        self._y = y
        n = len(y)

        if p.objective == "binary":
            self._loss = LogLoss()
            self.init_score = self._loss.init_score(y)
            score = np.full(n, self.init_score)
        else:
            self._loss = SoftmaxLoss(p.n_classes)
            self.init_score = self._loss.init_score(y)
            score = np.tile(self.init_score, (n, 1))

        self.cipher = get_cipher(p.cipher, **self._cipher_kwargs())
        self._n_parties = 1 + (len(self.remote_hosts)
                               if self.remote_hosts is not None
                               else len(X_hosts))
        return score

    def _bin(self, X) -> BinnedData:
        """Bin one party's features.  A pre-binned ``BinnedData`` passes
        through; a chunked ``RowBlocks`` source takes the out-of-core
        two-pass sketch path (§13); an in-memory array takes the
        monolithic exact-quantile fit."""
        p = self.params
        if isinstance(X, BinnedData):
            return X
        if isinstance(X, RowBlocks):
            return bin_features_stream(X, p.n_bins, sparse=p.sparse,
                                       use_pallas=p.use_pallas)
        return bin_features(X, p.n_bins, sparse=p.sparse,
                            use_pallas=p.use_pallas)

    @property
    def trees_per_round(self) -> int:
        """Trees one ``boost_round`` appends (the resume-boundary unit)."""
        if self.params.objective == "multiclass":
            return self.params.n_classes
        return max(1, self.params.forest_size)

    def boost_round(self, t: int, score: np.ndarray) -> np.ndarray:
        """Grow round ``t``'s tree(s) and return the updated score.

        Transactional against ``score`` and the model: the input score is
        never mutated and trees are appended only after every tree of the
        round finished — a mid-round fault leaves both exactly at the
        round boundary, and the randomness streams (GOSS, host shuffles)
        are keyed by the ABSOLUTE tree index, so a replay regrows
        bit-identical trees."""
        p = self.params
        if len(self.trees) != t * self.trees_per_round:
            raise RuntimeError(
                f"boost_round({t}) expects {t * self.trees_per_round} "
                f"trees, model has {len(self.trees)} — rollback_to_round "
                f"first")
        score = np.array(score, np.float64, copy=True)
        t0 = time.perf_counter_ns()
        # g/h are computed ONCE per round, for all classes (the paper's
        # default multiclass setting): recomputing inside the class loop
        # trained class c+1 on scores already updated by class c's tree
        with self.tracer.span("grad", round=t, rows=len(score)):
            g, h = self._loss.grad_hess(self._y, score)
        grown = []
        if p.objective == "multiclass":
            mix_party = self._mix_party(t, self._n_parties)
            ctxs, scheds = [], []
            for c in range(p.n_classes):
                ctx, sched = self._tree_ctx(
                    self.cipher, g[:, c], h[:, c], t, mix_party=mix_party,
                    tree_idx=t * p.n_classes + c)
                ctxs.append(ctx)
                scheds.append(sched)
            # cross-class prefetch (DESIGN.md §12): all class g/h of the
            # round are known up front, so class c+1's enc_gh encrypts and
            # ships on a pump thread WHILE class c grows.  One pump in
            # flight at a time: class c's broadcast always completes
            # before c+1's dispatches, keeping wire order sequential (and
            # the protocol bit-identical — only wall-clock overlap moves).
            pump = None
            for c in range(p.n_classes):
                ctx = ctxs[c]
                if pump is not None:
                    pump.join()
                    pump = None
                if p.pipeline:
                    if not ctx.enc_shipped and \
                            self._sched_has_host(scheds[c], len(ctx.hosts)):
                        _encrypt_all(ctx, ctx.g[ctx.sel_rows],
                                     ctx.h[ctx.sel_rows])
                    if c + 1 < p.n_classes and self._sched_has_host(
                            scheds[c + 1], len(ctxs[c + 1].hosts)):
                        nxt = ctxs[c + 1]
                        pump = _EncryptPump(nxt, nxt.g[nxt.sel_rows],
                                            nxt.h[nxt.sel_rows])
                with self.tracer.span("class", round=t, cls=c,
                                      tree=ctx.tree_idx):
                    tree, leaf_rows = grow_tree(ctx, scheds[c])
                grown.append((tree, c, leaf_rows))
            if pump is not None:      # defensive: last class never pumps
                pump.join()
        elif p.forest_size > 1:
            grown.extend(self._grow_forest(self.cipher, g, h, t))
        else:
            tree, leaf_rows = self._grow(
                self.cipher, g, h, t,
                mix_party=self._mix_party(t, self._n_parties),
                tree_idx=t)
            grown.append((tree, -1, leaf_rows))
        with self.tracer.span("score_update", round=t, rows=len(score),
                              trees=len(grown)):
            for tree, cls, leaf_rows in grown:
                self.trees.append(tree)
                self.tree_class.append(cls)
                self._apply(score, tree, leaf_rows, cls=cls)
        dt_ns = time.perf_counter_ns() - t0
        self.stats.tree_seconds.append(dt_ns * 1e-9)
        # "round" is the benchmark harness's own annotation: a record in
        # the ring only, never a profiler span (obs.trace.RESERVED)
        self.tracer.complete("round", t0, dt_ns, round=t)
        return score

    def rollback_to_round(self, t: int) -> None:
        """Truncate the model to the round-``t`` boundary (replay)."""
        keep = t * self.trees_per_round
        del self.trees[keep:]
        del self.tree_class[keep:]
        del self.stats.tree_seconds[t:]
        self._predictor = None
        self._predictor_n_trees = -1

    def finish_fit(self, score: np.ndarray):
        self.train_score_ = score
        return self

    def _cipher_kwargs(self):
        return cipher_kwargs(self.params)

    def _mix_party(self, t: int, n_parties: int):
        if self.params.tree_mode != "mix":
            return None
        cycle = t // max(1, self.params.trees_per_party)
        return cycle % n_parties        # 0 = guest, 1.. = host id + 1

    # ------------------------------------------------------------------
    def _make_hosts(self, cipher) -> list:
        if self.remote_hosts is not None:
            return self.remote_hosts    # one party per process (transport)
        p = self.params
        engines = [CipherHistogram(cipher, p.n_bins, sparse=p.sparse,
                                   use_pallas=p.use_pallas,
                                   stats=self.stats, mesh=p.mesh,
                                   tracer=self.tracer)
                   for _ in self.host_data]
        return [HostRuntime(hid=i, data=d, engine=e)
                for i, (d, e) in enumerate(zip(self.host_data, engines))]

    def _sched_has_host(self, sched, n_hosts: int) -> bool:
        if n_hosts == 0:
            return False
        if sched is None:
            return True
        return any(sched(d)[1] for d in range(self.params.max_depth))

    def _tree_ctx(self, cipher, g, h, t: int, mix_party=None,
                  tree_idx: int | None = None) -> tuple:
        """Build one tree's (TreeContext, schedule) without growing it —
        the pipelined driver needs the context early so the next tree's
        enc_gh can encrypt + ship while the current tree still splits."""
        p = self.params
        n = g.shape[0]
        # the ABSOLUTE index of the tree being grown.  Passed explicitly
        # by boost_round because the round commits its trees only at the
        # end (transactional replay), so len(self.trees) lags mid-round.
        if tree_idx is None:
            tree_idx = len(self.trees)
        if p.goss:
            # dedicated per-tree stream keyed by the GLOBAL tree counter:
            # host split-info shuffling must not perturb GOSS sampling (or
            # federated != local under GOSS), and a per-round key would
            # hand every class tree of a multiclass round the identical
            # subsample of the rest set
            goss_rng = np.random.default_rng((p.seed, tree_idx, 17))
            sel, w = goss_sample(g, p.top_rate, p.other_rate, goss_rng)
            g = g.copy(); h = h.copy()
            if g.ndim == 1:
                g[sel] *= w; h[sel] *= w
            else:
                g[sel] *= w[:, None]; h[sel] *= w[:, None]
        else:
            sel = np.arange(n)

        codec = self._make_codec(cipher, g[sel], h[sel])
        hosts = self._make_hosts(cipher)
        ctx = TreeContext(params=p, cipher=cipher, codec=codec,
                          channel=self.channel, stats=self.stats,
                          guest_data=self.guest_data, g=g, h=h, sel_rows=sel,
                          hosts=hosts, tree_idx=tree_idx)
        return ctx, self._schedule(mix_party, len(hosts))

    def _grow(self, cipher, g, h, t: int, mix_party=None,
              tree_idx: int | None = None) -> tuple:
        ctx, schedule = self._tree_ctx(cipher, g, h, t, mix_party=mix_party,
                                       tree_idx=tree_idx)
        return grow_tree(ctx, schedule)

    def _grow_forest(self, cipher, g, h, t: int) -> list:
        """One round-forest (FedGBF-style): ``forest_size`` bagged member
        trees sharing ONE enc_gh round-trip (``core/tree.py grow_forest``).
        Leaf weights grow with learning_rate / k so the round's additive
        update averages the members instead of k-times overshooting.
        Returns ``[(tree, -1, leaf_rows), ...]``."""
        p = self.params
        k = p.forest_size
        base = t * k                    # absolute index of the first member
        n = g.shape[0]
        if p.goss:
            # ONE GOSS pass per round, keyed by the round's base index:
            # members share the encrypted batch, so they must share the
            # selection it was built from — bags re-subsample within it
            goss_rng = np.random.default_rng((p.seed, base, 17))
            sel, w = goss_sample(g, p.top_rate, p.other_rate, goss_rng)
            g = g.copy(); h = h.copy()
            g[sel] *= w; h[sel] *= w
        else:
            sel = np.arange(n)
        bag_rng = np.random.default_rng((p.seed, base, 29))
        if p.forest_subsample >= 1.0:
            bags = [np.arange(len(sel)) for _ in range(k)]
        else:
            size = max(1, int(round(p.forest_subsample * len(sel))))
            bags = [np.sort(bag_rng.choice(len(sel), size, replace=False))
                    for _ in range(k)]

        codec = self._make_codec(cipher, g[sel], h[sel])
        hosts = self._make_hosts(cipher)
        fp = dataclasses.replace(p, learning_rate=p.learning_rate / k)
        ctx = TreeContext(params=fp, cipher=cipher, codec=codec,
                          channel=self.channel, stats=self.stats,
                          guest_data=self.guest_data, g=g, h=h, sel_rows=sel,
                          hosts=hosts, tree_idx=base, forest_k=k)
        schedule = self._schedule(self._mix_party(t, self._n_parties),
                                  len(hosts))
        members = grow_forest(ctx, bags, schedule)
        return [(tree, -1, leaf_rows) for tree, leaf_rows in members]

    def _schedule(self, mix_party, n_hosts: int):
        p = self.params
        if p.tree_mode == "mix" and mix_party is not None:
            if mix_party == 0:
                return lambda d: (True, [])
            return lambda d: (False, [mix_party - 1])
        if p.tree_mode == "layered":
            return lambda d: ((False, list(range(n_hosts)))
                              if d < p.host_depth else (True, []))
        return None

    def _make_codec(self, cipher, g, h):
        p = self.params
        if p.objective == "mo":
            plan = mo_encoding.plan_mo_packing(g, h, len(g),
                                               cipher.plaintext_bits,
                                               p.precision)
            return MOCodec(plan)
        if p.packing:
            plan = encoding.plan_packing(g, h, len(g), cipher.plaintext_bits,
                                         p.precision)
            return PackedCodec(plan)
        return NoPackCodec.plan(g, p.precision)

    # ------------------------------------------------------------------
    def _apply(self, score, tree: FederatedTree, leaf_rows: dict,
               cls: int = -1):
        """Training score update from the grower's train-side row->leaf
        map; ``leaf_rows`` never lives on the tree (serving/export must
        see no row-level state)."""
        for nd in tree.nodes:
            if nd.left == -1 and nd.weight is not None:
                rows = leaf_rows[nd.nid]
                if cls >= 0:
                    score[rows, cls] += nd.weight
                else:
                    score[rows] += nd.weight

    def _serving_predictor(self):
        """Cached packed serving engine over this model's trees, wired to
        the model's wire/stat ledgers (rebuilt if trees changed)."""
        from ..serving import FederatedPredictor, PackedEnsemble
        if self._predictor is None \
                or self._predictor_n_trees != len(self.trees):
            ens = PackedEnsemble.from_model(self)
            self._predictor = FederatedPredictor(
                ens.guest, ens.hosts, channel=self.channel,
                stats=self.stats, mesh=self.params.mesh,
                use_pallas=self.params.use_pallas)
            self._predictor_n_trees = len(self.trees)
        return self._predictor

    def predict_score(self, X_guest, X_hosts,
                      packed: bool = True) -> np.ndarray:
        """Raw ensemble scores.  ``packed=True`` (default) serves through
        the packed engine — bit-identical to the legacy loop, one wire
        round-trip per host per batch, counted under the ``predict_*``
        tags.  ``packed=False`` keeps the per-node ``predict_tree`` loop
        as the slow oracle (tests, benchmarks)."""
        if packed and self.trees:
            return self._serving_predictor().predict_score(X_guest, X_hosts)
        if self.remote_hosts is not None:
            raise ValueError(
                "host split tables live in remote processes: the "
                "predict_tree oracle cannot run here — serve through "
                "MultiHostRun.predict_score (per-party exports)")
        from .binning import apply_binning
        p = self.params
        gb = apply_binning(X_guest, self.guest_data, p.use_pallas)
        hb = [apply_binning(X, d, p.use_pallas)
              for X, d in zip(X_hosts, self.host_data)]
        n = gb.shape[0]
        if p.objective == "binary":
            score = np.full(n, self.init_score)
        else:
            score = np.tile(self.init_score, (n, 1))
        for tree, cls in zip(self.trees, self.tree_class):
            out = predict_tree(tree, gb, hb)
            if cls >= 0:
                score[:, cls] += out
            else:
                score += out
        return score

    def predict_proba(self, X_guest, X_hosts,
                      packed: bool = True) -> np.ndarray:
        from .loss import sigmoid, softmax
        s = self.predict_score(X_guest, X_hosts, packed=packed)
        return sigmoid(s) if self.params.objective == "binary" else softmax(s)


# ---------------------------------------------------------------------------
# the local plaintext baseline ("XGBoost" role in the paper's tables)
# ---------------------------------------------------------------------------

class LocalGBDT(VerticalBoosting):
    """Single-party plaintext GBDT with identical binning/gain/weights.

    Implemented as federated training with zero hosts and the plain cipher:
    the protocol collapses to local histogram split finding, which makes the
    parity claim ('lossless', paper Table 3) checkable in one code path.
    """

    def __init__(self, params: SBTParams):
        params = dataclasses.replace(params, cipher="plain", packing=True,
                                     compression=False, tree_mode="default")
        super().__init__(params)

    def fit(self, X: np.ndarray, y: np.ndarray):   # type: ignore[override]
        return super().fit(X, y, [])

    def predict_score(self, X, packed: bool = True) -> np.ndarray:  # type: ignore[override]
        return super().predict_score(X, [], packed=packed)

    def predict_proba(self, X, packed: bool = True) -> np.ndarray:  # type: ignore[override]
        from .loss import sigmoid, softmax
        s = self.predict_score(X, packed=packed)
        return sigmoid(s) if self.params.objective == "binary" else softmax(s)
