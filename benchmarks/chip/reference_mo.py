"""The plain reference for SecureBoost-MO: one multi-output tree a round,
written from the SecureBoost+ paper's equations and independent of the
program (it imports nothing of it and takes none of its tables).

As ``reference`` for binary trees, the federated model is the one a single
party holding every feature would train (arXiv:2110.10927, Table 3), so
one party trains over the guest's features followed by the host's.  What
changes with ``l`` classes (section 5.3):

* softmax loss: ``p = softmax(score)``, ``g_k = p_k - y_k`` (one-hot ``y``),
  ``h_k = max(p_k (1 - p_k), 1e-16)``, the diagonal Hessian of section
  5.3.1;
* gains of eq. (6) summed over the classes (eqs. (19)-(20)):
  ``0.5 (sum_k GL_k^2 / (HL_k + lambda) + sum_k GR_k^2 / (HR_k + lambda)
  - sum_k G_k^2 / (H_k + lambda))``;
* vector leaf weights ``-lr G_k / (H_k + lambda)`` (eq. (18)).

Binning, the candidate rules (``min_leaf``, ``min_gain``, the first best
candidate in feature order) and the tie rule are ``reference``'s: the
quantile thresholds and ``TIE`` are imported from it, not copied.

``dtype`` is the precision of every gradient, histogram, gain, weight and
score: float64 is the reference, float32 the control one precision below.
"""

from __future__ import annotations

import numpy as np

from reference import TIE, Tree


def softmax_grad(y: np.ndarray, score: np.ndarray, dtype=np.float64):
    """(g, h) of shape (n, l) for integer labels ``y`` and (n, l) logits."""
    s = np.asarray(score, dtype)
    z = s - s.max(axis=1, keepdims=True)
    e = np.exp(z)
    p = e / e.sum(axis=1, keepdims=True)
    onehot = (np.asarray(y).astype(np.int64)[:, None]
              == np.arange(s.shape[1])).astype(dtype)
    g = p - onehot
    h = np.maximum(p * (dtype(1.0) - p), dtype(1e-16))
    return g, h


def _direct_histograms(bins_t, direct: dict, g, h, n_bins, dtype):
    """(G, H) of shape (n_f, n_bins, l) and C of shape (n_f, n_bins) for
    each node in ``direct``."""
    n_f, n = bins_t.shape
    l = g.shape[1]
    nids = list(direct)
    sizes = [len(direct[nid]) for nid in nids]
    whole = len(nids) == 1 and sizes[0] == n
    rows = np.arange(n) if whole else np.concatenate([direct[i]
                                                      for i in nids])
    base = np.repeat(np.arange(len(nids), dtype=np.int64) * n_bins, sizes)
    gr, hr = g[rows], h[rows]                         # (r, l)
    size = len(nids) * n_bins
    G = np.zeros((n_f, size, l), dtype)
    H = np.zeros((n_f, size, l), dtype)
    C = np.zeros((n_f, size), np.int64)
    cls = np.arange(l, dtype=np.int64)
    for f in range(n_f):
        key = base + (bins_t[f] if whole else bins_t[f, rows])
        if dtype == np.float64:
            # one bincount over (cell, class) pairs, classes minor
            kc = (key[:, None] * l + cls).ravel()
            G[f] = np.bincount(kc, gr.ravel(), size * l).reshape(size, l)
            H[f] = np.bincount(kc, hr.ravel(), size * l).reshape(size, l)
        else:           # bincount adds in float64: accumulate in dtype
            np.add.at(G[f], key, gr)
            np.add.at(H[f], key, hr)
        C[f] = np.bincount(key, minlength=size)
    G = G.reshape(n_f, len(nids), n_bins, l)
    H = H.reshape(n_f, len(nids), n_bins, l)
    C = C.reshape(n_f, len(nids), n_bins)
    return {nid: (G[:, k], H[:, k], C[:, k]) for k, nid in enumerate(nids)}


def _best_split(G, H, C, G_tot, H_tot, n_rows, p, dtype):
    """``reference._best_split`` with the gain summed over classes."""
    lam, half = dtype(p["lam"]), dtype(0.5)
    GL = np.cumsum(G, axis=1, dtype=dtype)[:, :-1]
    HL = np.cumsum(H, axis=1, dtype=dtype)[:, :-1]
    CL = np.cumsum(C, axis=1)[:, :-1]
    GR, HR = G_tot - GL, H_tot - HL
    parent = (G_tot * G_tot / (H_tot + lam)).sum(dtype=dtype)
    gain = half * ((GL * GL / (HL + lam)).sum(axis=-1, dtype=dtype)
                   + (GR * GR / (HR + lam)).sum(axis=-1, dtype=dtype)
                   - parent)
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
    """One multi-output tree, level by level, the larger child's
    histograms taken as the parent's less the smaller child's, as
    ``reference.grow_tree``.  ``g``, ``h``: (n, l).  Returns ``(tree,
    update, open_rows)``: ``update[i]`` is the (l,) weight of the leaf row
    ``i`` lands in, and ``open_rows`` marks the rows of open nodes."""
    n, l = g.shape
    lr, lam = dtype(p["learning_rate"]), dtype(p["lam"])
    tree = Tree([], [], [], [], [], [], [])
    update = np.zeros((n, l), dtype)
    open_rows = np.zeros(n, bool)

    def new_node():
        for col in (tree.feature, tree.bin, tree.left, tree.right):
            col.append(-1)
        tree.alts.append([])
        tree.open.append(False)
        tree.weight.append(None)
        return len(tree.weight) - 1

    def make_leaf(nid, rows):
        G = g[rows].sum(axis=0, dtype=dtype)
        H = h[rows].sum(axis=0, dtype=dtype)
        tree.weight[nid] = (-lr * G / (H + lam)).astype(dtype)
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
            G_tot = g[rows].sum(axis=0, dtype=dtype)
            H_tot = h[rows].sum(axis=0, dtype=dtype)
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


def update_gap(got: np.ndarray, want: np.ndarray,
               open_rows: np.ndarray) -> float:
    """The largest difference between an update ``got`` (n, l) and the
    reference's ``want`` over the rows no tie leaves open and every class,
    as a share of ``want``'s largest magnitude (inf where not finite)."""
    scale = max(float(np.abs(want).max()), np.finfo(np.float64).tiny)
    diff = np.abs(np.asarray(got, np.float64) - want)[~open_rows]
    gap = float(diff.max()) / scale if diff.size else 0.0
    return gap if gap == gap else np.inf
