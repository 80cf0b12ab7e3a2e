"""Layer-batched histogram path: kernel parity, engine parity, e2e parity.

The batched pipeline (one kernel launch / reduce / cumsum / round-trip per
tree layer) must be bit-identical to the per-node path it replaced; these
tests pin that at every level.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import LocalGBDT, SBTParams, VerticalBoosting
from repro.core.binning import bin_features
from repro.core.he import get_cipher
from repro.core.histogram import CipherHistogram
from repro.core.party import Stats
from repro.kernels.histogram import (hist_ref, layer_ciphertext_histogram,
                                     layer_count_histogram, layer_hist_ref)

# shapes chosen to exercise non-divisible instance / feature / node blocks
LAYER_SHAPES = [(300, 5, 16, 32, 3), (257, 9, 8, 16, 1), (64, 3, 4, 8, 9),
                (1024, 17, 12, 32, 5), (1, 1, 4, 4, 2),
                # feature counts the (8, 128) block rule once refused on TPU
                (300, 14, 16, 32, 3), (257, 33, 8, 16, 5),
                (64, 1000, 4, 8, 2)]


@pytest.mark.parametrize("n_i,n_f,L,n_b,n_n", LAYER_SHAPES)
def test_layer_kernel_vs_ref_and_per_node_oracle(n_i, n_f, L, n_b, n_n):
    rng = np.random.default_rng(n_i * 7 + n_n)
    bins = rng.integers(0, n_b, (n_i, n_f)).astype(np.int32)
    bins[rng.random((n_i, n_f)) < 0.15] = -1          # masked (sparse) cells
    slot = rng.integers(-1, n_n, n_i).astype(np.int32)  # -1 = no direct node
    cts = rng.integers(0, 256, (n_i, L)).astype(np.int32)
    out = np.asarray(layer_ciphertext_histogram(bins, slot, cts, n_n, n_b,
                                                use_pallas=True))
    ref = np.asarray(layer_hist_ref(jnp.asarray(bins), jnp.asarray(slot),
                                    jnp.asarray(cts), n_n, n_b))
    np.testing.assert_array_equal(out, ref)
    # each node slice equals the single-node oracle on its masked rows
    for k in range(n_n):
        masked = np.where(slot[:, None] == k, bins, -1)
        per_node = np.asarray(hist_ref(jnp.asarray(masked),
                                       jnp.asarray(cts), n_b))
        np.testing.assert_array_equal(out[k], per_node)


def test_layer_kernel_all_masked():
    bins = np.full((50, 4), -1, np.int32)
    slot = np.zeros(50, np.int32)
    cts = np.random.default_rng(0).integers(0, 256, (50, 8)).astype(np.int32)
    out = np.asarray(layer_ciphertext_histogram(bins, slot, cts, 2, 8))
    assert (out == 0).all()


def test_layer_count_histogram_matches_bincount():
    rng = np.random.default_rng(3)
    n_i, n_f, n_b, n_n = 400, 6, 16, 4
    bins = rng.integers(0, n_b, (n_i, n_f)).astype(np.int32)
    slot = rng.integers(-1, n_n, n_i).astype(np.int32)
    cnt = np.asarray(layer_count_histogram(bins, slot, n_n, n_b))
    for k in range(n_n):
        for f in range(n_f):
            expect = np.bincount(bins[slot == k, f], minlength=n_b)
            np.testing.assert_array_equal(cnt[k, f], expect)


@pytest.mark.parametrize("cipher_name,kw", [
    ("plain", {"bits": 256}),
    ("affine", {"key_bits": 192, "seed": 7}),
])
def test_layer_histograms_match_per_node_engine(cipher_name, kw):
    """Batched direct + lazy-subtract accumulation vs node_histogram /
    subtract, for both limb ciphers, through the frontier state."""
    from repro.core.frontier import CipherFrontier

    rng = np.random.default_rng(11)
    n, n_f, n_b = 160, 4, 8
    cipher = get_cipher(cipher_name, **kw)
    X = rng.normal(0, 1, (n, n_f)).astype(np.float32)
    data = bin_features(X, n_b)
    pts = rng.integers(0, 2**40, n)
    cts = np.asarray(cipher.encrypt_ints([int(v) for v in pts]))
    cts = cts.reshape(n, 1, -1)

    engine = CipherHistogram(cipher, n_b, stats=Stats())
    frontier = CipherFrontier(engine, data, cts)
    # one parent node split into two children; right child by subtraction
    parent_rows = np.arange(n)
    left_rows = np.arange(n // 3)
    right_rows = np.arange(n // 3, n)
    cache = {0: engine.node_histogram(data, cts, parent_rows)}
    frontier.store(0, *cache[0])

    batched = frontier.layer_histograms(
        {1: left_rows, 2: right_rows}, direct=[1], subtract=[(2, 0, 1)])
    h1, c1 = engine.node_histogram(data, cts, left_rows)
    h2, c2 = engine.subtract(cache[0], (h1, c1))
    np.testing.assert_array_equal(np.asarray(batched[1][0]), np.asarray(h1))
    np.testing.assert_array_equal(batched[1][1], c1)
    np.testing.assert_array_equal(np.asarray(batched[2][0]), np.asarray(h2))
    np.testing.assert_array_equal(batched[2][1], c2)
    assert engine.stats.n_hist_launches >= 1
    # decrypted bin sums must equal plaintext bin sums
    from repro.core.he import limbs
    dec = limbs.to_pyints(np.asarray(
        cipher.decrypt_limbs(jnp.asarray(batched[2][0]))
        if cipher_name == "affine" else batched[2][0]))
    dec = np.asarray(dec, dtype=object).reshape(n_f, n_b)
    for f in range(n_f):
        for b in range(n_b):
            expect = int(sum(int(v) for v, bb in
                             zip(pts[right_rows], data.bins[right_rows, f])
                             if bb == b))
            assert int(dec[f, b]) == expect, (f, b)


def test_paillier_add_at_matches_loop():
    cipher = get_cipher("paillier", key_bits=128, seed=5)
    rng = np.random.default_rng(2)
    k, m, n_slots = 40, 6, 2
    vals = cipher.encrypt_ints([int(v) for v in
                                rng.integers(0, 1000, k * n_slots)])
    vals = vals.reshape(k, n_slots)
    idx = rng.integers(0, m, k)
    acc_fast = cipher.zero((m, n_slots))
    cipher.add_at(acc_fast, idx, vals)
    acc_slow = cipher.zero((m, n_slots))
    for i in range(k):
        acc_slow[idx[i]] = cipher.add(acc_slow[idx[i]], vals[i])
    dec_fast = cipher.decrypt_to_ints(acc_fast)
    dec_slow = cipher.decrypt_to_ints(acc_slow)
    assert dec_fast == dec_slow


def test_layer_batched_grower_bit_identical_and_o_depth():
    """End-to-end: federated (plain cipher) == local baseline bit-for-bit
    under the layer-batched grower, and kernel launches / split_infos
    round-trips per tree are O(depth), not O(#nodes)."""
    rng = np.random.default_rng(0)
    X = rng.normal(0, 1, (500, 6)).astype(np.float32)
    w = rng.normal(0, 1, 6)
    y = (X @ w + 0.3 * rng.normal(0, 1, 500) > 0).astype(np.float64)

    n_trees, max_depth = 3, 4
    loc = LocalGBDT(SBTParams(n_trees=n_trees, max_depth=max_depth,
                              n_bins=16)).fit(X, y)
    fed = VerticalBoosting(SBTParams(n_trees=n_trees, max_depth=max_depth,
                                     n_bins=16, cipher="plain")).fit(
        X[:, :3], y, [X[:, 3:]])
    np.testing.assert_array_equal(fed.predict_proba(X[:, :3], [X[:, 3:]]),
                                  loc.predict_proba(X))

    n_internal = sum(1 for t in fed.trees for nd in t.nodes if nd.left != -1)
    assert fed.stats.n_split_roundtrips <= n_trees * max_depth
    assert fed.stats.n_hist_launches <= n_trees * max_depth
    assert n_internal > n_trees * max_depth      # the collapse is real
    # channel: exactly one split_infos message per (layer, host) pair
    assert fed.channel.msgs["split_infos"] == fed.stats.n_split_roundtrips
