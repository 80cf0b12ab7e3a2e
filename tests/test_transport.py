"""Multi-host party runtime (DESIGN.md §10): payload codec, framing, and
process-per-party training/serving bit-identity against the in-process
Channel oracle — with identical per-tag wire-byte ledgers.

The loopback tests run the full message path (encode -> frame -> decode ->
handler) single-threaded in this process; the socket test spawns a REAL
second OS process for the host and drives the identical protocol over
localhost TCP.
"""

import os
import tempfile
from pathlib import Path

import numpy as np
import pytest

from repro.core import SBTParams, VerticalBoosting
from repro.runtime.transport import (HOST_PLATFORM, KIND_CTRL, KIND_PROTO,
                                     LoopbackEndpoint, MultiHostRun,
                                     TransportError,
                                     decode_frame, decode_payload,
                                     encode_frame, encode_payload)

PROTOCOL_TAGS = {"enc_gh", "assign_sync", "split_infos", "chosen_sid",
                 "assign_mask"}
SERVING_TAGS = {"predict_req", "predict_bits"}


def _data(n=300, d=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(0, 1, (n, d)).astype(np.float32)
    w = rng.normal(0, 1, d)
    y = (X @ w + 0.3 * rng.normal(0, 1, n) > 0).astype(np.float64)
    return X, y


# ---------------------------------------------------------------------------
# payload codec
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("obj", [
    None, True, False, 0, -7, 2 ** 62, -(2 ** 100), 2 ** 2048 + 13,
    1.5, -0.0, "tag", b"\x00\xffraw",
    (1, "two", None), [1, [2, [3]]],
    {"a": 1, "b": {"c": (None, 2.5)}, 3: "int-key"},
])
def test_codec_scalars_and_containers(obj):
    assert decode_payload(encode_payload(obj)) == obj


@pytest.mark.parametrize("arr", [
    np.arange(12, dtype=np.int32).reshape(3, 4),
    np.arange(6, dtype=np.int64),
    np.zeros((0, 5), np.float32),
    np.random.default_rng(0).integers(0, 256, (4, 2, 7)).astype(np.uint8),
    np.asarray([[True, False], [False, True]]),
    np.float64(3.25) * np.ones((2, 1)),
    np.asarray(2.5),                    # 0-d
])
def test_codec_ndarrays_exact(arr):
    out = decode_payload(encode_payload(arr))
    assert out.dtype == arr.dtype and out.shape == arr.shape
    np.testing.assert_array_equal(out, arr)


def test_codec_limb_tensor_and_jax_array():
    import jax.numpy as jnp
    limbs = np.random.default_rng(1).integers(0, 256, (5, 2, 9)).astype(
        np.int32)
    out = decode_payload(encode_payload(jnp.asarray(limbs)))
    assert out.dtype == np.int32
    np.testing.assert_array_equal(out, limbs)


def test_codec_object_int_array():
    """Paillier ciphertexts: object arrays of python bigints."""
    rng = np.random.default_rng(2)
    vals = [int(v) ** 7 + 1 for v in rng.integers(2, 2 ** 40, 6)]
    arr = np.asarray(vals, dtype=object).reshape(2, 3)
    out = decode_payload(encode_payload(arr))
    assert out.dtype == object and out.shape == (2, 3)
    assert out.reshape(-1).tolist() == vals


def test_codec_rejects_unserializable():
    with pytest.raises(TransportError):
        encode_payload(object())
    with pytest.raises(TransportError):
        encode_payload(np.asarray([{"not": "an int"}], dtype=object))


def test_codec_rejects_trailing_garbage():
    with pytest.raises(TransportError):
        decode_payload(encode_payload(1) + b"x")


# ---------------------------------------------------------------------------
# framing + endpoints
# ---------------------------------------------------------------------------

def test_frame_roundtrip():
    payload = {"data": np.arange(8, dtype=np.int32), "m": 4}
    frame = encode_frame(KIND_PROTO, "host0", "guest", "split_infos", 1234,
                         payload, seq=42)
    kind, src, dst, tag, seq, nbytes, out = decode_frame(frame)
    assert (kind, src, dst, tag, seq, nbytes) == (
        KIND_PROTO, "host0", "guest", "split_infos", 42, 1234)
    np.testing.assert_array_equal(out["data"], payload["data"])
    ctrl = encode_frame(KIND_CTRL, "guest", "host0", "bye", 0, None)
    assert decode_frame(ctrl)[0] == KIND_CTRL


def test_loopback_endpoint_delivery_and_close():
    a, b = LoopbackEndpoint.pair()
    a.send_bytes(b"frame-1")
    a.send_bytes(b"frame-2")
    assert b.poll()
    assert b.recv_bytes() == b"frame-1"
    assert b.recv_bytes() == b"frame-2"
    assert not b.poll()
    with pytest.raises(TransportError):
        b.recv_bytes()          # empty inbox = protocol desync
    b.close()
    with pytest.raises(TransportError):
        a.send_bytes(b"after close")


# ---------------------------------------------------------------------------
# 2-process-equivalent training/serving vs the in-process oracle (loopback)
# ---------------------------------------------------------------------------

def _bit_identity_run(params, X, y, n_guest_cols, n_hosts=1,
                      transport="loopback"):
    """Train + serve both in-process and over the transport; return both
    runs for assertions."""
    cols = np.array_split(np.arange(X.shape[1] - n_guest_cols) + n_guest_cols,
                          n_hosts)
    Xg = X[:, :n_guest_cols]
    Xh = [X[:, c] for c in cols]
    ref = VerticalBoosting(params).fit(Xg, y, Xh)
    run = MultiHostRun(params, Xh, transport=transport,
                       export_dir=tempfile.mkdtemp())
    model = run.fit(Xg, y)
    return ref, run, model, Xg, Xh


def test_loopback_training_bit_identical_affine_goss_compress():
    """The flagship parity: affine limb ciphertexts, GOSS row selection,
    cipher compression — all protocol features crossing a serialized
    transport — must train bit-identically to the in-process oracle with
    the identical per-tag ledger."""
    X, y = _data(n=400)
    params = SBTParams(n_trees=3, max_depth=3, n_bins=16, cipher="affine",
                       key_bits=256, precision=20, goss=True, seed=3)
    ref, run, model, Xg, Xh = _bit_identity_run(params, X, y, 3)
    try:
        np.testing.assert_array_equal(model.train_score_, ref.train_score_)
        # identical per-tag wire ledger (bytes AND message counts)
        assert run.channel.summary() == ref.channel.summary()
        assert PROTOCOL_TAGS <= set(run.channel.summary())
        # unchanged round-trip shape: one split_infos per (layer, host)
        assert model.stats.n_split_roundtrips == ref.stats.n_split_roundtrips
        # host-side HE work, merged back, matches the shared-Stats oracle
        merged = run.merged_stats()
        for k in ("n_encrypt", "n_decrypt", "n_hom_add", "n_hom_scalar",
                  "n_split_infos", "n_packages", "n_hist_launches"):
            assert getattr(merged, k) == getattr(ref.stats, k), k
        # placement locality is per-process: the remote host re-places the
        # deserialized ciphertexts onto ITS device (one placement per
        # tree), where the in-process run adopts them born-sharded
        assert merged.n_cts_placements == params.n_trees
        # the host party's cipher holds NO private material: decrypting
        # the guest's gradients from inside the host process must be
        # impossible, not merely unexercised
        host_cipher = run.parties[0].cipher
        for attr in ("T_dec", "T_enc", "a_inv_int", "a_int"):
            assert not hasattr(host_cipher, attr), attr
        with pytest.raises(AttributeError):
            host_cipher.decrypt_limbs(run.parties[0].hr.cts[:1, 0])
    finally:
        run.close()


def test_loopback_serving_bit_identical_from_reloaded_exports():
    """Round-batched serving across the transport: each party serves from
    its RELOADED export half, one predict_bits round-trip per host per
    batch, bit-identical scores, identical predict-tag ledgers."""
    X, y = _data(n=350, seed=1)
    params = SBTParams(n_trees=3, max_depth=3, n_bins=16, cipher="affine",
                       key_bits=256, precision=20, seed=5)
    ref, run, model, Xg, Xh = _bit_identity_run(params, X, y, 3)
    try:
        run.serve()
        Xe, _ = _data(n=123, seed=9)
        s_remote = run.predict_score(Xe[:, :3], [Xe[:, 3:]])
        s_ref = ref.predict_score(Xe[:, :3], [Xe[:, 3:]])
        np.testing.assert_array_equal(s_remote, s_ref)
        assert run.channel.summary() == ref.channel.summary()
        assert SERVING_TAGS <= set(run.channel.summary())
        assert (model.stats.n_predict_roundtrips
                == ref.stats.n_predict_roundtrips == 1)
        # counted once, at the guest collect site: folding host stats in
        # must NOT double it
        assert run.merged_stats().n_predict_roundtrips == 1
        # the host process exported its own half; reload it here and check
        # it matches the oracle's in-process export byte for byte
        from repro.serving import PackedEnsemble, load_host
        h_remote = load_host(os.path.join(run.export_dir, "host0"))
        h_ref = PackedEnsemble.from_model(ref).hosts[0]
        np.testing.assert_array_equal(h_remote.table.fid, h_ref.table.fid)
        np.testing.assert_array_equal(h_remote.table.bid, h_ref.table.bid)
        np.testing.assert_array_equal(h_remote.thresholds, h_ref.thresholds)
    finally:
        run.close()


def test_loopback_two_hosts_and_multiclass():
    X, y4 = _data(n=300, d=8, seed=2)
    s = X @ np.ones(8)
    y = ((s > np.quantile(s, 0.33)).astype(float)
         + (s > np.quantile(s, 0.66)).astype(float))
    params = SBTParams(n_trees=2, max_depth=2, n_bins=8,
                       objective="multiclass", n_classes=3)
    ref, run, model, Xg, Xh = _bit_identity_run(params, X, y, 2, n_hosts=2)
    try:
        np.testing.assert_array_equal(model.train_score_, ref.train_score_)
        assert run.channel.summary() == ref.channel.summary()
        run.serve()
        np.testing.assert_array_equal(
            run.predict_score(X[:, :2], staged=True),
            ref.predict_score(Xg, Xh))
    finally:
        run.close()


def test_loopback_paillier_object_arrays_on_the_wire():
    """The python-int oracle cipher: ciphertexts travel as object arrays
    through the codec (real bigints, no limb tensors)."""
    X, y = _data(n=100, seed=4)
    params = SBTParams(n_trees=1, max_depth=2, n_bins=8, cipher="paillier",
                       key_bits=256, precision=16)
    ref, run, model, Xg, Xh = _bit_identity_run(params, X, y, 3)
    try:
        np.testing.assert_array_equal(model.train_score_, ref.train_score_)
        assert run.channel.summary() == ref.channel.summary()
        # the Paillier private key (_lam/_mu) never exists host-side
        host_cipher = run.parties[0].cipher
        assert not hasattr(host_cipher, "_lam")
        assert not hasattr(host_cipher, "_mu")
        with pytest.raises(AttributeError):
            host_cipher.decrypt_to_ints(run.parties[0].hr.cts[:1, 0])
    finally:
        run.close()


def test_unstaged_serving_batch_fails_loudly():
    """Serving eval rows the host never received must raise an actionable
    error, not silently pair eval guest features with training host
    rows."""
    X, y = _data(n=80, seed=7)
    params = SBTParams(n_trees=1, max_depth=2, n_bins=8)
    run = MultiHostRun(params, [X[:, 3:]], transport="loopback",
                       export_dir=tempfile.mkdtemp())
    try:
        run.fit(X[:, :3], y)
        run.serve()
        Xbig, _ = _data(n=200, seed=8)
        # harness guard: neither X_hosts nor staged=True -> refuse before
        # any wire traffic
        with pytest.raises(ValueError, match="not staged"):
            run.predict_score(Xbig[:, :3])
        # host-side guard: staged=True asserted falsely, batch larger
        # than the staged matrix -> the host rejects with an actionable
        # message instead of dying opaquely
        with pytest.raises(TransportError, match="stage"):
            run.predict_score(Xbig[:, :3], staged=True)
        # staged properly, the same batch serves fine
        s = run.predict_score(Xbig[:, :3], [Xbig[:, 3:]])
        assert s.shape == (200,)
    finally:
        run.close()


def test_refit_resets_per_fit_accounting():
    """A second fit() on the same long-lived run must report per-fit
    ledgers and merged stats, not the accumulation of both fits."""
    X, y = _data(n=150, seed=11)
    params = SBTParams(n_trees=1, max_depth=2, n_bins=8)
    ref = VerticalBoosting(params).fit(X[:, :3], y, [X[:, 3:]])
    run = MultiHostRun(params, [X[:, 3:]], transport="loopback")
    try:
        run.fit(X[:, :3], y)
        model2 = run.fit(X[:, :3], y)           # refit on the same run
        np.testing.assert_array_equal(model2.train_score_,
                                      ref.train_score_)
        assert run.channel.summary() == ref.channel.summary()
        merged = run.merged_stats()
        assert merged.n_hom_add == ref.stats.n_hom_add
        assert merged.n_hist_launches == ref.stats.n_hist_launches
    finally:
        run.close()


def test_binned_serving_refuses_remote_hosts():
    """predict_score_binned would silently ignore caller bins for a
    remote host (its process bins its own staged rows) — it must refuse."""
    X, y = _data(n=100, seed=12)
    params = SBTParams(n_trees=1, max_depth=2, n_bins=8)
    run = MultiHostRun(params, [X[:, 3:]], transport="loopback")
    try:
        run.fit(X[:, :3], y)
        pred = run.serve()
        with pytest.raises(ValueError, match="in-process halves"):
            pred.predict_score_binned(np.zeros((4, 3), np.int32),
                                      [np.zeros((4, 3), np.int32)])
    finally:
        run.close()


def test_remote_model_refuses_inprocess_packing():
    X, y = _data(n=120, seed=6)
    params = SBTParams(n_trees=1, max_depth=2, n_bins=8)
    run = MultiHostRun(params, [X[:, 3:]], transport="loopback")
    try:
        model = run.fit(X[:, :3], y)
        from repro.serving import PackedEnsemble
        with pytest.raises(ValueError, match="remote processes"):
            PackedEnsemble.from_model(model)
        # the legacy predict_tree oracle reads host tables the guest
        # process does not have: guided error, not a bare KeyError
        with pytest.raises(ValueError, match="remote processes"):
            model.predict_score(X[:, :3], [None], packed=False)
    finally:
        run.close()


# ---------------------------------------------------------------------------
# the real thing: one OS process per party over localhost TCP
# ---------------------------------------------------------------------------

def test_socket_two_process_training_and_serving_bit_identical():
    """Forced-2-process run (guest here, host spawned) over the
    length-prefixed socket transport: training AND packed serving are
    bit-identical to the in-process Channel run, with identical per-tag
    wire-byte ledgers and unchanged round-trip counts — and the socket
    moved at least as many framed bytes as the analytic ledger counts."""
    X, y = _data(n=250)
    params = SBTParams(n_trees=2, max_depth=3, n_bins=16, cipher="plain")
    Xg, Xh = X[:, :3], [X[:, 3:]]
    ref = VerticalBoosting(params).fit(Xg, y, Xh)
    run = MultiHostRun(params, Xh, transport="socket",
                       export_dir=tempfile.mkdtemp(), timeout=300.0)
    try:
        model = run.fit(Xg, y)
        np.testing.assert_array_equal(model.train_score_, ref.train_score_)
        assert run.channel.summary() == ref.channel.summary()
        assert model.stats.n_split_roundtrips == ref.stats.n_split_roundtrips

        run.serve()
        np.testing.assert_array_equal(run.predict_score(Xg, staged=True),
                                      ref.predict_score(Xg, Xh))
        assert (model.stats.n_predict_roundtrips
                == ref.stats.n_predict_roundtrips == 1)
        assert (PROTOCOL_TAGS | SERVING_TAGS) <= set(run.channel.summary())

        # framed socket traffic >= analytic guest->host ledger bytes (the
        # ledger counts protocol fidelity; frames add headers and the
        # in-memory limb layout)
        for tag in ("enc_gh", "assign_sync", "chosen_sid", "predict_req"):
            assert run.channel.tx_bytes[tag] > run.channel.totals[tag]
        assert run.ping() < 5.0
        merged = run.merged_stats()
        assert merged.n_hom_add == ref.stats.n_hom_add
        assert merged.n_hist_launches == ref.stats.n_hist_launches
    finally:
        run.close()


def test_socket_children_run_jax_on_the_stated_platform(capfd, monkeypatch):
    """Spawned host parties run JAX on ``HOST_PLATFORM``, set in the child
    before it imports JAX whatever this process's own setting, so a child
    never races its parent for an accelerator; the parent's environment is
    left as it was."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    X, _ = _data(n=64)
    params = SBTParams(n_trees=1, max_depth=2, n_bins=8, cipher="plain")
    run = MultiHostRun(params, [X[:, 3:]], transport="socket", timeout=300.0)
    run.close()
    assert "JAX_PLATFORMS" not in os.environ
    assert (f"host0: JAX_PLATFORMS={HOST_PLATFORM} backend={HOST_PLATFORM}"
            in capfd.readouterr().out)


def test_bench_transport_raises_instead_of_falling_back(monkeypatch):
    """A socket run that cannot start fails the transport benchmark: it
    never quietly measures the in-memory loopback instead."""
    monkeypatch.syspath_prepend(str(Path(__file__).resolve().parents[1]))
    from benchmarks import bench_transport

    class NoSpawn:
        def __init__(self, *a, transport="socket", **k):
            if transport != "loopback":
                raise OSError("process spawning unavailable")

    class Oracle:
        def __init__(self, params):
            pass

        def fit(self, *a):
            return self

    monkeypatch.setattr(bench_transport, "MultiHostRun", NoSpawn)
    monkeypatch.setattr(bench_transport, "VerticalBoosting", Oracle)
    with pytest.raises(OSError, match="spawning unavailable"):
        bench_transport.main(quick=True)
