"""The plain reference's binning follows numpy's quantile rule, and its
tie bookkeeping marks exactly the rows a tied split point leaves open."""

import numpy as np
import pytest

import reference


@pytest.mark.parametrize("n", [2, 7, 100, 1001])
def test_thresholds_follow_numpy_quantile(n):
    rng = np.random.default_rng(n)
    X = rng.standard_normal((n, 40)).astype(np.float32)
    X[:, 3] = np.round(X[:, 3])               # duplicate quantiles
    X[:, 5] = 1.0                             # a constant feature
    qs = np.linspace(0, 1, 33)[1:-1]
    want = np.quantile(X.astype(np.float64), qs, axis=0).T.astype(np.float32)
    got = reference.quantile_thresholds(X, 32)
    for f in range(40):
        uniq = np.unique(want[f])
        assert np.array_equal(got[f, :len(uniq)], uniq)
        assert np.all(np.isinf(got[f, len(uniq):]))


def test_bins_count_thresholds_at_or_below():
    rng = np.random.default_rng(3)
    X = rng.standard_normal((300, 37)).astype(np.float32)
    thr = reference.quantile_thresholds(X, 16)
    bins = reference.bin_features(X, thr)
    want = np.stack([np.searchsorted(thr[f], X[:, f], side="right")
                     for f in range(37)])
    assert np.array_equal(bins, want)


P1 = dict(max_depth=1, n_bins=8, learning_rate=0.3, lam=1.0, min_leaf=1,
          min_gain=1e-6)


def test_equivalent_split_points_mark_ambiguous_rows():
    # 8 bins; feature 0 has training rows only in bins 0-2 and 6-7, so the
    # split after its bin 2 splits them as those after bins 3, 4 and 5
    # do; feature 1 splits them the same way round the other side
    bins_t = np.array([[0, 1, 2, 2, 6, 7, 7, 6],
                       [7, 7, 6, 6, 1, 0, 0, 1]], np.uint8)
    y = np.array([0, 0, 0, 0, 1, 1, 1, 1], float)
    g, h = reference.log_loss_grad(y, np.zeros(8))
    tree, _, open_rows = reference.grow_tree(bins_t, g, h, P1)
    assert (tree.feature[0], tree.bin[0]) == (0, 2)
    assert not tree.open[0] and not open_rows.any()
    assert sorted(tree.alts[0]) == [(0, 3, True), (0, 4, True),
                                    (0, 5, True), (1, 1, False),
                                    (1, 2, False), (1, 3, False),
                                    (1, 4, False), (1, 5, False)]
    serve = np.array([[2, 3, 5, 6, 0],
                      [6, 6, 6, 1, 3]], np.uint8)
    _, amb = reference.predict(0.0, [tree], serve)
    assert amb.tolist() == [False, True, True, False, True]


def test_tied_gains_that_split_rows_otherwise_open_the_node():
    # rows 0-3 and 4-7 hold the same gradients in the same mix, so
    # feature 0 (rows 0, 1 | rest) and feature 1 (rows 4, 5 | rest) tie
    # exactly while splitting the rows otherwise
    bins_t = np.array([[0, 0, 1, 1, 1, 1, 1, 1],
                       [1, 1, 1, 1, 0, 0, 1, 1]], np.uint8)
    y = np.array([1, 1, 0, 0, 1, 1, 0, 0], float)
    g, h = reference.log_loss_grad(y, np.zeros(8))
    tree, update, open_rows = reference.grow_tree(bins_t, g, h,
                                                  dict(P1, n_bins=2))
    assert tree.feature[0] == 0 and tree.open[0]
    assert open_rows.all()
    _, amb = reference.predict(0.0, [tree], bins_t)
    assert amb.all()


def test_float32_control_departs_from_float64():
    rng = np.random.default_rng(5)
    bins_t = rng.integers(0, 16, (6, 5000)).astype(np.uint8)
    y = (rng.random(5000) < 0.5).astype(float)
    score = rng.normal(0, 1, 5000)
    p = dict(max_depth=3, n_bins=16, learning_rate=0.3, lam=1.0,
             min_leaf=1, min_gain=1e-6)
    u64 = reference.grow_tree(bins_t, *reference.log_loss_grad(y, score),
                              p)[1]
    u32 = reference.grow_tree(
        bins_t, *reference.log_loss_grad(y, score, np.float32), p,
        np.float32)[1]
    gap = np.abs(u32 - u64).max() / np.abs(u64).max()
    assert 1e-9 < gap
