"""Pipelined boosting (DESIGN.md §12): rounds in flight, round-forests,
and async transport overlap.

The load-bearing claim is bit-identity: with ``forest_size=1``, a
pipelined run — encrypt pump, dual-buffer enc_gh staging, broker inbox —
must produce byte-for-byte the trees, scores, and converged per-tag
ledgers of the sequential run; the pipeline may only move work in TIME,
never change it.  Round-forests (``forest_size=k``) are a different
model by design, so their parity axis is plain-vs-affine cipher
bit-identity and kernel-vs-reference equality instead.

Single-device tests always run; sharded tests need the forced
multi-device CPU (``XLA_FLAGS=--xla_force_host_platform_device_count=8``)
and skip otherwise.  Socket tests spawn real host processes.
"""

import dataclasses
import math
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import SBTParams, VerticalBoosting
from repro.core.party import Stats
from repro.runtime.chaos import RECV, Delay, FaultPlan
from repro.runtime.transport import MultiHostRun

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count>=2")


def _data(n=300, d=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(0, 1, (n, d)).astype(np.float32)
    w = rng.normal(0, 1, d)
    y = (X @ w + 0.3 * rng.normal(0, 1, n) > 0).astype(np.float64)
    return X, y


def _data3(n=300, d=8, seed=0):
    X, _ = _data(n, d, seed)
    s = X @ np.ones(d)
    y = ((s > np.quantile(s, 0.33)).astype(float)
         + (s > np.quantile(s, 0.66)).astype(float))
    return X, y


def _sigs(model):
    return [t.signature() for t in model.trees]


# ---------------------------------------------------------------------------
# satellite: overlap_fraction / wire_overlap_frac zero-guards
# ---------------------------------------------------------------------------

def test_wire_overlap_frac_zero_encrypt_guard():
    """A run that never encrypts (plain cipher) records
    encrypt_seconds == 0; the derived overlap fraction must be exactly
    0.0 — not NaN, not a ZeroDivisionError."""
    s = Stats()
    assert s.wire_overlap_frac == 0.0
    s.prefetch_seconds = 0.5            # pathological: prefetch w/o encrypt
    assert s.wire_overlap_frac == 0.0
    s.encrypt_seconds = float("nan")
    assert s.wire_overlap_frac == 0.0
    s.encrypt_seconds = 1.0
    assert s.wire_overlap_frac == 0.5
    s.prefetch_seconds = 7.0            # clamped: hidden <= total by defn
    assert s.wire_overlap_frac == 1.0
    assert math.isfinite(s.overlap_fraction)


def test_plain_run_overlap_fractions_finite():
    X, y = _data(n=150)
    p = SBTParams(n_trees=2, max_depth=2, n_bins=8, cipher="plain",
                  pipeline=True, seed=1)
    m = VerticalBoosting(p).fit(X[:, :3], y, [X[:, 3:]])
    # plain runs still time their (no-op) encrypt step, so the guard's
    # zero-denominator branch is synthetic-only (test above); the live
    # invariant is clamping and finiteness
    assert 0.0 <= m.stats.wire_overlap_frac <= 1.0
    assert math.isfinite(m.stats.overlap_fraction)
    d = m.stats.as_dict()
    assert all(math.isfinite(v) for v in d.values()
               if isinstance(v, float))


# ---------------------------------------------------------------------------
# tentpole: pipelined == sequential bit-identity (in-process)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cipher", ["plain", "affine"])
@pytest.mark.parametrize("objective", ["binary", "multiclass"])
def test_pipelined_bit_identical_inprocess(cipher, objective):
    if objective == "multiclass":
        X, y = _data3(n=250)
        extra = dict(objective="multiclass", n_classes=3)
    else:
        X, y = _data(n=250)
        extra = {}
    kw = (dict(key_bits=256, precision=20) if cipher == "affine" else {})
    base = SBTParams(n_trees=2, max_depth=3, n_bins=16, cipher=cipher,
                     goss=True, seed=3, **extra, **kw)
    Xg, Xh = X[:, :3], [X[:, 3:]]
    seq = VerticalBoosting(dataclasses.replace(base, pipeline=False)).fit(
        Xg, y, [h.copy() for h in Xh])
    pipe = VerticalBoosting(dataclasses.replace(base, pipeline=True)).fit(
        Xg, y, Xh)
    np.testing.assert_array_equal(pipe.train_score_, seq.train_score_)
    assert _sigs(pipe) == _sigs(seq)
    # identical wire ledger: the pump moved the encrypt in time, not the
    # protocol in shape
    assert pipe.channel.summary() == seq.channel.summary()
    if cipher == "affine" and objective == "multiclass":
        # cross-class prefetch: class c+1's gradients are known at round
        # start, so its encrypt hides behind class c's growth
        assert pipe.stats.wire_overlap_frac > 0.0
        assert pipe.stats.prefetch_seconds > 0.0


# ---------------------------------------------------------------------------
# tentpole: pipelined == sequential over the transports
# ---------------------------------------------------------------------------

def _transport_pair(params, X, y, transport, n_hosts=2):
    Xg = X[:, :3]
    cols = np.array_split(np.arange(X.shape[1] - 3) + 3, n_hosts)
    Xh = [X[:, c] for c in cols]
    seq = VerticalBoosting(dataclasses.replace(params, pipeline=False)).fit(
        Xg, y, [h.copy() for h in Xh])
    run = MultiHostRun(params, Xh, transport=transport,
                       export_dir=tempfile.mkdtemp())
    return seq, run, Xg, Xh


def test_pipelined_loopback_bit_identical_and_staged():
    """Loopback: the guest's encrypt pump delivers the next class's
    enc_gh mid-tree; the PartyProcess must stage it (dual-buffer) and
    activate at the first assign_sync of the new tree — bit-identically,
    with converged ledgers, and with the stage->activate path actually
    exercised."""
    X, y = _data3(n=250)
    params = SBTParams(n_trees=2, max_depth=3, n_bins=16, cipher="affine",
                       key_bits=256, precision=20, objective="multiclass",
                       n_classes=3, pipeline=True, seed=7)
    seq, run, Xg, Xh = _transport_pair(params, X, y, "loopback")
    try:
        model = run.fit(Xg, y)
        np.testing.assert_array_equal(model.train_score_, seq.train_score_)
        assert _sigs(model) == _sigs(seq)
        assert run.channel.summary() == seq.channel.summary()
        # out-of-order arrival really happened: enc_gh frames for a
        # future tree were accepted and staged while a tree was in flight
        assert sum(pp.staged_activations for pp in run.parties) > 0
        assert model.stats.wire_overlap_frac > 0.0
        # serving from the per-party exports stays bit-identical too
        run.serve()
        np.testing.assert_array_equal(
            run.predict_score(Xg, staged=True),
            seq.predict_score(Xg, Xh))
    finally:
        run.close()


def test_pipelined_socket_bit_identical():
    """Forced-2-process acceptance: pipelined training over real sockets
    (broker inbox active on the hosts) is bit-identical to the
    sequential in-process oracle with identical converged per-tag
    ledgers."""
    X, y = _data3(n=200)
    params = SBTParams(n_trees=2, max_depth=3, n_bins=8, cipher="affine",
                       key_bits=256, precision=20, objective="multiclass",
                       n_classes=3, pipeline=True, seed=5)
    seq, run, Xg, Xh = _transport_pair(params, X, y, "socket", n_hosts=1)
    try:
        model = run.fit(Xg, y)
        np.testing.assert_array_equal(model.train_score_, seq.train_score_)
        assert _sigs(model) == _sigs(seq)
        assert run.channel.summary() == seq.channel.summary()
    finally:
        run.close()


def test_pipelined_socket_chaos_delayed_enc_gh():
    """Chaos: delay the prefetched enc_gh frames on the host's receive
    path — the broker's per-tag inbox absorbs the perturbed arrival
    timing (late prefetch, compute already waiting) without changing a
    single byte of the result."""
    X, y = _data3(n=150)
    params = SBTParams(n_trees=2, max_depth=2, n_bins=8, cipher="affine",
                       key_bits=256, precision=20, objective="multiclass",
                       n_classes=3, pipeline=True, seed=9)
    plans = {0: FaultPlan(rules=[
        Delay(tag="enc_gh", nth=2, direction=RECV, seconds=0.2),
        Delay(tag="enc_gh", nth=4, direction=RECV, seconds=0.2),
    ], seed=17)}
    Xg, Xh = X[:, :3], [X[:, 3:]]
    seq = VerticalBoosting(dataclasses.replace(params, pipeline=False)).fit(
        Xg, y, [Xh[0].copy()])
    run = MultiHostRun(params, Xh, transport="socket",
                       export_dir=tempfile.mkdtemp(), fault_plans=plans,
                       timeout=120.0)
    try:
        model = run.fit(Xg, y)
        np.testing.assert_array_equal(model.train_score_, seq.train_score_)
        assert _sigs(model) == _sigs(seq)
        assert run.channel.summary() == seq.channel.summary()
    finally:
        run.close()


def test_pipeline_resilient_incompatible():
    X, y = _data(n=100)
    params = SBTParams(n_trees=1, max_depth=2, n_bins=8, pipeline=True)
    run = MultiHostRun(params, [X[:, 3:]], transport="loopback")
    try:
        with pytest.raises(ValueError, match="resilient"):
            run.fit(X[:, :3], y, resilient=True, ckpt_dir=None)
    finally:
        run.close()


# ---------------------------------------------------------------------------
# round-forests (forest_size = k)
# ---------------------------------------------------------------------------

def test_forest_grows_k_trees_per_round_and_cipher_parity():
    """k bagged member trees per round off ONE enc_gh; the affine cipher
    pipeline must agree bit-for-bit with the plain debugging cipher on
    every member's structure."""
    X, y = _data(n=250)
    base = SBTParams(n_trees=2, max_depth=3, n_bins=16, forest_size=3,
                     seed=11)
    Xg, Xh = X[:, :3], [X[:, 3:]]
    plain = VerticalBoosting(base).fit(Xg, y, [Xh[0].copy()])
    aff = VerticalBoosting(dataclasses.replace(
        base, cipher="affine", key_bits=256, precision=20)).fit(Xg, y, Xh)
    assert len(plain.trees) == 2 * 3 == len(aff.trees)
    assert plain.trees_per_round == 3
    assert _sigs(aff) == _sigs(plain)
    np.testing.assert_array_equal(aff.train_score_, plain.train_score_)
    # one enc_gh round-trip per ROUND, not per member tree
    assert aff.channel.msgs["enc_gh"] == 2


def test_forest_requires_binary():
    X, y = _data3(n=100)
    p = SBTParams(n_trees=1, max_depth=2, n_bins=8, forest_size=2,
                  objective="multiclass", n_classes=3)
    with pytest.raises(ValueError, match="forest_size"):
        VerticalBoosting(p).fit(X[:, :3], y, [X[:, 3:]])


def test_forest_transport_bit_identical():
    """Round-forest training over the framed transport == in-process,
    including serving from per-member split tables (table_sinks demux)."""
    X, y = _data(n=200)
    params = SBTParams(n_trees=2, max_depth=3, n_bins=8, cipher="affine",
                       key_bits=256, precision=20, forest_size=3,
                       pipeline=True, seed=13)
    seq, run, Xg, Xh = _transport_pair(params, X, y, "loopback")
    try:
        model = run.fit(Xg, y)
        np.testing.assert_array_equal(model.train_score_, seq.train_score_)
        assert _sigs(model) == _sigs(seq)
        assert run.channel.summary() == seq.channel.summary()
        run.serve()
        np.testing.assert_array_equal(
            run.predict_score(Xg, staged=True),
            seq.predict_score(Xg, Xh))
        # the host demuxed its combined gid table into one local-nid
        # table per member tree (what serving export keys on)
        pp = run.parties[0]
        assert sorted(pp.tables) == list(range(2 * 3))
    finally:
        run.close()


@pytest.mark.parametrize("n_f,k", [(5, 3), (14, 1), (14, 4), (33, 1),
                                   (33, 4), (1000, 1), (1000, 4)])
def test_forest_kernel_matches_reference(n_f, k):
    """The (tree, node)-batched Pallas launch == the einsum reference on
    random masked inputs, at feature counts and member counts the TPU's
    (8, 128) block rule once refused."""
    from repro.kernels.histogram import (forest_ciphertext_histogram,
                                         forest_hist_ref)
    rng = np.random.default_rng(n_f * 10 + k)
    n_i, n_b, n_nodes, L = 257, 8, 4, 6
    bins = rng.integers(-1, n_b, (n_i, n_f)).astype(np.int32)
    slot = rng.integers(-1, n_nodes, (n_i, k)).astype(np.int32)
    cts = rng.integers(0, 256, (n_i, L)).astype(np.int32)
    ref = forest_hist_ref(jnp.asarray(bins), jnp.asarray(slot),
                          jnp.asarray(cts), n_nodes, n_b)
    out = forest_ciphertext_histogram(bins, slot, cts, n_nodes, n_b,
                                      use_pallas=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert out.shape == (k, n_nodes, n_f, n_b, L)


# ---------------------------------------------------------------------------
# sharded layer cumsum + sharded forest dispatch (forced multi-device)
# ---------------------------------------------------------------------------

@multi_device
def test_sharded_forest_training_bit_identical():
    """Full federated forest training on the forced mesh == single
    device, member for member."""
    from repro.launch.mesh import make_gbdt_mesh
    X, y = _data(n=512)
    base = SBTParams(n_trees=1, max_depth=3, n_bins=8, cipher="affine",
                     key_bits=256, precision=20, forest_size=3, seed=2)
    Xg, Xh = X[:, :3], [X[:, 3:]]
    one = VerticalBoosting(base).fit(Xg, y, [Xh[0].copy()])
    mesh = make_gbdt_mesh()
    many = VerticalBoosting(dataclasses.replace(base, mesh=mesh)).fit(
        Xg, y, Xh)
    assert _sigs(many) == _sigs(one)
    np.testing.assert_array_equal(many.train_score_, one.train_score_)


@multi_device
def test_sharded_cumsum_bit_identical_and_gated():
    """The ciphertext-domain layer cumsum shards over 'data' above the
    same >=256-rows-per-shard gate as the batched decrypt; below the
    gate it must fall back (return None) rather than pad-shard tiny
    layers."""
    from repro.core.binning import bin_features
    from repro.core.he import get_cipher
    from repro.core.histogram import CipherHistogram
    from repro.launch.mesh import make_gbdt_mesh

    cipher = get_cipher("affine", key_bits=256)
    mesh = make_gbdt_mesh()
    dd = dict(mesh.shape).get("data", 1)
    rng = np.random.default_rng(0)

    single = CipherHistogram(cipher, n_bins=16, use_pallas=False)
    sharded = CipherHistogram(cipher, n_bins=16, use_pallas=False,
                              mesh=mesh)
    # (nodes, features, bins, slots, L): leading axes flatten to the
    # group extent G = nodes*features the gate tests against; 64*dd nodes
    # of 4 features lands exactly at G = 256*dd = BLOCK_N*dd
    Ln = cipher.Ln
    big = rng.integers(0, 200, (64 * dd, 4, 16, 1, Ln)).astype(np.int32)
    wide = np.pad(big, [(0, 0)] * 4 + [(0, cipher.hist_width - Ln)])
    out = sharded._sharded_cumsum(jnp.asarray(wide), 2)
    assert out is not None          # the gate admitted this layer
    ref = np.asarray(single.cumsum(jnp.asarray(big)))
    np.testing.assert_array_equal(np.asarray(out), ref)
    np.testing.assert_array_equal(
        np.asarray(sharded.cumsum(jnp.asarray(big))), ref)

    small = rng.integers(0, 200, (2, 2, 16, 1, Ln)).astype(np.int32)
    assert sharded._sharded_cumsum(
        jnp.asarray(np.pad(small, [(0, 0)] * 4
                           + [(0, cipher.hist_width - Ln)])), 2) is None
    np.testing.assert_array_equal(
        np.asarray(sharded.cumsum(jnp.asarray(small))),
        np.asarray(single.cumsum(jnp.asarray(small))))
