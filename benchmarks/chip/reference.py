"""The plain reference: a straightforward gradient-boosted tree learner,
written from the SecureBoost+ paper's equations and independent of the
program (it imports nothing of it and takes none of its tables).

Vertical federated learning is lossless (arXiv:2110.10927, Table 3): the
federated model is the one a single party holding every feature would
train.  So the reference trains one party over the guest's features
followed by the host's, with

* quantile binning: thresholds at the ``n_bins - 1`` inner quantiles by
  numpy's linear rule on float64 of the float32 values, stored float32,
  duplicates collapsed to +inf; ``bin = #{t : x >= thr[t]}``;
* binary log loss: ``g = p - y``, ``h = max(p (1 - p), 1e-16)``;
* gains of eq. (6), a candidate with fewer than ``min_leaf`` rows on a side
  refused, a split taken only above ``min_gain``, the first best candidate
  in feature order winning;
* leaf weights of eq. (7), ``-lr G / (H + lambda)``, summed over the leaf's
  rows in row order.

``dtype`` is the precision of every gradient, histogram, gain, weight and
score: float64 is the reference, float32 the control one precision below
it.  Binning compares float32 values with float32 thresholds, which is
exact in either.

Ties.  Gains that are equal in exact arithmetic can come out in either
order in floating point: candidates that split a node's rows alike
(thresholds of one feature with empty bins between them; in a small node,
other features that separate its few rows the same way, either way
round), and, after the first tree, candidates whose rows differ but hold
the same gradient values, since all rows of one leaf and label share
them.  The protocol may pick any of them (a host's candidates travel
shuffled, and its sums are exact where the guest's are float64).  So a
candidate whose gain lies within ``TIE`` of the best one is kept: where
it splits the node's rows alike it joins the split's ``alts``, and a row
scored later that it routes the other way is ambiguous; where it splits
them otherwise, or where the best gain ties with ``min_gain``, the node is
open, and its rows (training and scored alike) are ambiguous: either
choice is correct for them.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def quantile_thresholds(X: np.ndarray, n_bins: int) -> np.ndarray:
    """(n_f, n_bins - 1) float32 split points, +inf padded: numpy's
    ``quantile(float64(X), q, axis=0)`` with the linear rule, each
    feature's duplicates collapsed."""
    n_f = X.shape[1]
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    q = np.quantile(X.astype(np.float64), qs, axis=0).T.astype(np.float32)
    out = np.full((n_f, n_bins - 1), np.inf, np.float32)
    for f, row in enumerate(q):
        uniq = np.unique(row)
        out[f, :len(uniq)] = uniq
    return out


def bin_features(X: np.ndarray, thr: np.ndarray) -> np.ndarray:
    """(n_f, n) uint8 bins, feature-major: ``#{t : x >= thr[t]}``."""
    return np.stack([np.searchsorted(thr[f], X[:, f], side="right")
                     for f in range(X.shape[1])]).astype(np.uint8)


def bin_parties(parts: list, n_bins: int):
    """Thresholds and feature-major bins of the parties' matrices, their
    features in party order (guest first)."""
    thr = [quantile_thresholds(X, n_bins) for X in parts]
    bins = [bin_features(X, t) for X, t in zip(parts, thr)]
    return np.concatenate(thr), np.concatenate(bins)


@dataclasses.dataclass
class Tree:
    feature: list      # per node: feature index, -1 for a leaf
    bin: list          # go left iff bin <= this
    alts: list         # (feature, bin, same side) splitting rows alike
    open: list         # a tie with a candidate that splits rows otherwise
    left: list
    right: list
    weight: list       # leaf weight (None for an inner node)


def init_score(y: np.ndarray) -> float:
    p = min(max(float(np.mean(y)), 1e-6), 1 - 1e-6)
    return float(np.log(p / (1 - p)))


def log_loss_grad(y: np.ndarray, score: np.ndarray, dtype=np.float64):
    one = dtype(1.0)
    s = np.asarray(score, dtype)
    p = one / (one + np.exp(-s))
    g = p - np.asarray(y, dtype)
    h = np.maximum(p * (one - p), dtype(1e-16))
    return g, h


def _direct_histograms(bins_t, direct: dict, g, h, n_bins, dtype):
    """(G, H, C) of shape (n_f, n_bins) for each node in ``direct``."""
    n_f, n = bins_t.shape
    nids = list(direct)
    sizes = [len(direct[nid]) for nid in nids]
    whole = len(nids) == 1 and sizes[0] == n
    rows = np.arange(n) if whole else np.concatenate([direct[i]
                                                      for i in nids])
    base = np.repeat(np.arange(len(nids), dtype=np.int64) * n_bins, sizes)
    gr, hr = g[rows], h[rows]
    size = len(nids) * n_bins
    G = np.zeros((n_f, size), dtype)
    H = np.zeros((n_f, size), dtype)
    C = np.zeros((n_f, size), np.int64)
    for f in range(n_f):
        key = base + (bins_t[f] if whole else bins_t[f, rows])
        if dtype == np.float64:
            G[f] = np.bincount(key, gr, size)
            H[f] = np.bincount(key, hr, size)
        else:           # bincount adds in float64: accumulate in dtype
            np.add.at(G[f], key, gr)
            np.add.at(H[f], key, hr)
        C[f] = np.bincount(key, minlength=size)
    shape = (n_f, len(nids), n_bins)
    G, H, C = (a.reshape(shape) for a in (G, H, C))
    return {nid: (G[:, k], H[:, k], C[:, k]) for k, nid in enumerate(nids)}


TIE = 1e-12    # gains closer than this share of the best one tie


def _best_split(G, H, C, G_tot, H_tot, n_rows, p, dtype):
    """The node's best split: ``(feature, bin, tied)``, with every other
    candidate whose gain ties with it, or None; and whether the split or
    leaf decision ties with ``min_gain``."""
    lam, half = dtype(p["lam"]), dtype(0.5)
    GL = np.cumsum(G, axis=1, dtype=dtype)[:, :-1]
    HL = np.cumsum(H, axis=1, dtype=dtype)[:, :-1]
    CL = np.cumsum(C, axis=1)[:, :-1]
    GR, HR = G_tot - GL, H_tot - HL
    parent = G_tot * G_tot / (H_tot + lam)
    gain = half * (GL * GL / (HL + lam) + GR * GR / (HR + lam) - parent)
    ok = (CL >= p["min_leaf"]) & (n_rows - CL >= p["min_leaf"])
    gain = np.where(ok, gain, -np.inf)
    f, b = divmod(int(np.argmax(gain)), gain.shape[1])
    best = float(gain[f, b])
    near = abs(best - p["min_gain"]) <= TIE * max(abs(best), p["min_gain"])
    if not best > p["min_gain"]:
        return None, near
    tied = [(int(f2), int(b2))
            for f2, b2 in np.argwhere(gain >= best - TIE * abs(best))
            if (f2, b2) != (f, b)]
    return (f, b, tied), near


def grow_tree(bins_t: np.ndarray, g: np.ndarray, h: np.ndarray, p: dict,
              dtype=np.float64):
    """One tree, level by level, the larger child's histograms taken as
    the parent's less the smaller child's.  Returns ``(tree, update,
    open_rows)``: ``update[i]`` is the weight of the leaf row ``i`` lands
    in, and ``open_rows`` marks the rows of open nodes, whose updates the
    program may rightly set otherwise."""
    n = bins_t.shape[1]
    lr, lam = dtype(p["learning_rate"]), dtype(p["lam"])
    tree = Tree([], [], [], [], [], [], [])
    update = np.zeros(n, dtype)
    open_rows = np.zeros(n, bool)

    def new_node():
        for col in (tree.feature, tree.bin, tree.left, tree.right):
            col.append(-1)
        tree.alts.append([])
        tree.open.append(False)
        tree.weight.append(None)
        return len(tree.weight) - 1

    def make_leaf(nid, rows):
        G = g[rows].sum(dtype=dtype)
        H = h[rows].sum(dtype=dtype)
        tree.weight[nid] = dtype(-lr * G / (H + lam))
        update[rows] = tree.weight[nid]

    def make_open(nid, rows):
        tree.open[nid] = True
        open_rows[rows] = True

    rows_of = {new_node(): np.arange(n)}
    parent_hist: dict = {}      # child -> (parent's hists, sibling)
    for _ in range(p["max_depth"]):
        split = {}
        for nid, rows in rows_of.items():
            if len(rows) >= 2 * p["min_leaf"] and len(rows) > 0:
                split[nid] = rows
            else:
                make_leaf(nid, rows)
        direct = {nid: r for nid, r in split.items()
                  if nid not in parent_hist
                  or parent_hist[nid][1] not in split}
        hists = _direct_histograms(bins_t, direct, g, h, p["n_bins"], dtype)
        for nid in split.keys() - direct.keys():
            (pG, pH, pC), sib = parent_hist[nid]
            sG, sH, sC = hists[sib]
            hists[nid] = (pG - sG, pH - sH, pC - sC)
        nxt, parent_hist = {}, {}
        for nid, rows in split.items():
            G_tot = g[rows].sum(dtype=dtype)
            H_tot = h[rows].sum(dtype=dtype)
            best, near = _best_split(*hists[nid], G_tot, H_tot, len(rows),
                                     p, dtype)
            if near:
                make_open(nid, rows)
            if best is None:
                make_leaf(nid, rows)
                continue
            f, b, tied = best
            left = bins_t[f, rows] <= b
            for f2, b2 in tied:
                other = bins_t[f2, rows] <= b2
                if np.array_equal(other, left):
                    tree.alts[nid].append((f2, b2, True))
                elif np.array_equal(other, ~left):
                    tree.alts[nid].append((f2, b2, False))
                else:
                    make_open(nid, rows)
            lid, rid = new_node(), new_node()
            tree.feature[nid], tree.bin[nid] = f, b
            tree.left[nid], tree.right[nid] = lid, rid
            nxt[lid], nxt[rid] = rows[left], rows[~left]
            small, large = ((lid, rid) if left.sum() <= len(rows) / 2
                            else (rid, lid))
            parent_hist[large] = (hists[nid], small)
        rows_of = nxt
    for nid, rows in rows_of.items():
        make_leaf(nid, rows)
    return tree, update, open_rows


def _route(tree: Tree, bins_t: np.ndarray, out: np.ndarray,
           ambiguous: np.ndarray) -> None:
    """Add each row's leaf weight to ``out``; flag tie-ambiguous rows."""
    stack = [(0, np.arange(bins_t.shape[1]))]
    while stack:
        nid, rows = stack.pop()
        if tree.open[nid]:
            ambiguous[rows] = True
        f = tree.feature[nid]
        if f < 0:
            out[rows] += tree.weight[nid]
            continue
        left = bins_t[f, rows] <= tree.bin[nid]
        for f2, b2, same in tree.alts[nid]:
            other = (bins_t[f2, rows] <= b2) == same
            ambiguous[rows[other != left]] = True
        stack.append((tree.left[nid], rows[left]))
        stack.append((tree.right[nid], rows[~left]))


def predict(base: float, trees: list, bins_t: np.ndarray,
            dtype=np.float64):
    """(probabilities, ambiguous-row mask) of feature-major binned rows."""
    n = bins_t.shape[1]
    score = np.full(n, base, dtype)
    ambiguous = np.zeros(n, bool)
    for tree in trees:
        update = np.zeros(n, dtype)
        _route(tree, bins_t, update, ambiguous)
        score = score + update
    one = dtype(1.0)
    return one / (one + np.exp(-score)), ambiguous
