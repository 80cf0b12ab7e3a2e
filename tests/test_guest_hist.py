"""Guest plaintext layer histograms: the per-feature ``np.bincount`` path.

``PlainHistogram.layer_histograms`` accumulates a layer's direct nodes with
one ``np.bincount`` per feature (and per output column) over a feature-major
composite index.  ``bincount`` adds each cell's weights in row order from
0.0, as ``np.add.at`` does, so every histogram must equal the ``np.add.at``
one bit for bit: per node (``node_histogram``), against the layer body the
bincount path replaced, and through whole fits.
"""

import numpy as np
import pytest

from repro.core import SBTParams, VerticalBoosting
from repro.core.binning import BinnedData
from repro.core.histogram import PlainHistogram
from repro.core.party import Stats

N_B = 8


def _add_at_layer(self, data, g, h, node_rows, direct, subtract, cache):
    """The ``np.add.at`` layer body the bincount path replaced."""
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
        bins = data.bins[rows_cat]
        gr, hr = g[rows_cat], h[rows_cat]
        comp = slot_cat[:, None] * n_b + bins
        zmask = data.zero_mask[rows_cat] if sparse else None
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
            np.add.at(gt, slot_cat, gr)
            np.add.at(ht, slot_cat, hr)
            ct += np.bincount(slot_cat, minlength=n_d)
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


def _data(n, n_f, sparse, dtype, seed):
    """Random bins; feature 0 puts every row in one bin."""
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, N_B, (n, n_f)).astype(dtype)
    bins[:, 0] = 3
    zero_bins = zero_mask = None
    if sparse:
        zero_bins = rng.integers(0, N_B, n_f).astype(np.int32)
        zero_mask = rng.random((n, n_f)) < 0.3
        bins = np.where(zero_mask, zero_bins[None, :], bins).astype(dtype)
    return BinnedData(bins=bins, thresholds=np.zeros((n_f, N_B - 1),
                                                     np.float32),
                      n_bins=N_B, zero_bins=zero_bins, zero_mask=zero_mask)


def _gh(n, n_classes, seed):
    rng = np.random.default_rng(seed + 1)
    shape = (n,) if n_classes is None else (n, n_classes)
    return rng.standard_normal(shape), rng.random(shape)


def _node_rows(n, n_nodes, seed, empty=True):
    """Disjoint sorted row sets; with ``empty`` the last node has no rows."""
    rng = np.random.default_rng(seed + 2)
    cut = np.sort(rng.choice(np.arange(1, n), n_nodes - 1, replace=False))
    parts = np.split(rng.permutation(n), cut)
    if empty and n_nodes > 1:
        parts[-2] = np.concatenate([parts[-2], parts[-1]])
        parts[-1] = parts[-1][:0]
    return {k: np.sort(p).astype(np.int64) for k, p in enumerate(parts)}


def _equal(a, b):
    return all(np.array_equal(x, y) for x, y in zip(a, b))


@pytest.mark.parametrize("n_direct", [1, 2, 8, 16])
@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
@pytest.mark.parametrize("n_classes", [None, 3, 10],
                         ids=["binary", "mo3", "mo10"])
def test_layer_histograms_equal_add_at(n_classes, sparse, n_direct):
    """Direct nodes (one of them empty) equal the ``np.add.at`` layer body
    bit for bit and, dense, the per-node ``node_histogram`` oracle."""
    n, n_f = 240, 5
    data = _data(n, n_f, sparse, np.int32, seed=n_direct)
    g, h = _gh(n, n_classes, seed=n_direct)
    rows = _node_rows(n, n_direct, seed=n_direct)
    direct = list(rows)
    stats = Stats()
    eng = PlainHistogram(N_B, sparse=sparse, stats=stats)
    got = eng.layer_histograms(data, g, h, rows, direct, [], {})
    want = _add_at_layer(eng, data, g, h, rows, direct, [], {})
    for nid in direct:
        assert _equal(got[nid], want[nid])
        G, H, C = got[nid]
        assert G.shape == (n_f, N_B) + g.shape[1:]
        assert C.sum() == n_f * len(rows[nid])
        if not sparse:
            assert _equal(got[nid], eng.node_histogram(data, g, h, rows[nid]))
    assert (stats.n_guest_hist_bincount, stats.n_guest_hist_add_at) == \
        (n_direct, 0)
    assert eng.path == "bincount"


@pytest.mark.parametrize("dtype", [np.int8, np.int32])
@pytest.mark.parametrize("n_classes", [None, 3], ids=["binary", "mo3"])
def test_direct_subtract_mix_equals_per_node_oracle(n_classes, dtype):
    """Half the layer direct, half parent - sibling from the cache: every
    node equals the per-node ``np.add.at`` oracle (or its difference)."""
    n, n_f = 300, 6
    data = _data(n, n_f, False, dtype, seed=5)
    g, h = _gh(n, n_classes, seed=5)
    eng = PlainHistogram(N_B)
    parents = _node_rows(n, 4, seed=7, empty=False)
    cache = {p: eng.node_histogram(data, g, h, r) for p, r in parents.items()}
    rows, direct, subtract = {}, [], []
    for p, r in parents.items():
        left, right = 10 + 2 * p, 11 + 2 * p
        rows[left], rows[right] = r[: len(r) // 3], r[len(r) // 3:]
        direct.append(left)
        subtract.append((right, p, left))
    got = eng.layer_histograms(data, g, h, rows, direct, subtract, cache)
    for nid in direct:
        assert _equal(got[nid], eng.node_histogram(data, g, h, rows[nid]))
    for nid, par, sib in subtract:
        assert _equal(got[nid], eng.subtract(
            cache[par], eng.node_histogram(data, g, h, rows[sib])))
    assert _equal(got[10], _add_at_layer(eng, data, g, h, rows, direct,
                                         subtract, cache)[10])


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
@pytest.mark.parametrize("n_classes", [None, 10], ids=["binary", "mo10"])
def test_row_blocks_keep_add_at_and_match_bincount(n_classes, sparse):
    """``row_block`` smaller than a node runs the ``np.add.at`` chunks, and
    they equal the bincount path bit for bit."""
    n, n_f = 200, 4
    data = _data(n, n_f, sparse, np.int32, seed=9)
    g, h = _gh(n, n_classes, seed=9)
    rows = _node_rows(n, 3, seed=9)
    direct = list(rows)
    stats = Stats()
    blocked = PlainHistogram(N_B, sparse=sparse, row_block=7, stats=stats)
    whole = PlainHistogram(N_B, sparse=sparse)
    got = blocked.layer_histograms(data, g, h, rows, direct, [], {})
    want = whole.layer_histograms(data, g, h, rows, direct, [], {})
    assert all(_equal(got[nid], want[nid]) for nid in direct)
    assert blocked.path == "add_at"
    assert (stats.n_guest_hist_bincount, stats.n_guest_hist_add_at) == (0, 3)


def _fit(objective, **kw):
    rng = np.random.default_rng(3)
    X = rng.normal(0, 1, (240, 7)).astype(np.float32)
    if objective == "mo":
        W = rng.normal(0, 1, (7, 3))
        y = np.argmax(X @ W + 0.5 * rng.normal(0, 1, (240, 3)),
                      axis=1).astype(np.float64)
        kw.update(objective="mo", n_classes=3)
    else:
        y = (X[:, 0] + X[:, 4] > 0) * 1.0
    params = SBTParams(cipher="plain", n_trees=2, max_depth=3, n_bins=16,
                       seed=2, **kw)
    model = VerticalBoosting(params).fit(X[:, :3], y, [X[:, 3:]])
    return model, model.predict_score(X[:, :3], [X[:, 3:]])


def _recording(layer, seen):
    def record(self, *args):
        out = layer(self, *args)
        seen.append({nid: out[nid] for nid in sorted(out)})
        return out
    return record


@pytest.mark.parametrize("objective", ["mo", "binary"])
def test_fit_is_the_same_with_the_add_at_layer_forced(objective,
                                                      monkeypatch):
    """Whole fits grow the same trees and scores, from the same layer
    histograms, with the guest's layer histograms forced back onto the
    ``np.add.at`` body."""
    seen_new, seen_old = [], []
    monkeypatch.setattr(PlainHistogram, "layer_histograms", _recording(
        PlainHistogram.layer_histograms, seen_new))
    new, new_score = _fit(objective)
    monkeypatch.setattr(PlainHistogram, "layer_histograms",
                        _recording(_add_at_layer, seen_old))
    old, old_score = _fit(objective)
    assert [t.signature() for t in new.trees] == \
        [t.signature() for t in old.trees]
    assert np.array_equal(new_score, old_score)
    assert len(seen_new) == len(seen_old) > 0
    for a, b in zip(seen_new, seen_old):
        assert list(a) == list(b)
        assert all(_equal(a[nid], b[nid]) for nid in a)
    assert new.stats.n_guest_hist_bincount > 0
    assert old.stats.n_guest_hist_bincount == 0


@pytest.mark.parametrize("row_block,path", [(0, "bincount"),
                                            (64, "add_at")])
def test_guest_hist_counters_and_span_path(row_block, path):
    """Every direct node the guest accumulates is counted under the path
    that ran, and every ``guest_hist`` span names that path."""
    model, _ = _fit("mo", trace=True, row_block=row_block)
    s = model.stats
    taken = getattr(s, f"n_guest_hist_{path}")
    assert taken > 0
    assert s.n_guest_hist_bincount + s.n_guest_hist_add_at == taken
    spans = [e[6] for e in model.tracer.export_events()
             if e[0] == "X" and e[1] == "guest_hist"]
    assert spans and {a["path"] for a in spans} == {path}
