"""The MO guest's device decode (``MOCodec.decode_device``): each class's
g and h field turned into float64 bits on the device
(``encoding.fields_float64_bits``), against ``float(int)``, the host word
decode (``decode_limbs``) and the python-int ``decode``, bit for bit."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SBTParams, VerticalBoosting, encoding, mo_encoding
from repro.core.he import limbs
from repro.core.tree import MOCodec, _decode_pyints

N_CAPACITY = 3_103


def _embedded(rnd, values, off, width, bits=1020):
    """Random big integers with ``values`` in bits [off, off + width)."""
    out = []
    for v in values:
        noise = rnd.getrandbits(bits) & ~(((1 << width) - 1) << off)
        out.append(noise | (v << off))
    return out


def _field_values(rnd, width):
    """Values of a ``width``-bit field that reach every rounding case."""
    vals = [0, (1 << width) - 1, 1, 1 << (width - 1)]
    vals += [rnd.getrandbits(width) | (1 << (width - 1)) for _ in range(40)]
    for top in range(54, width + 1):
        vals.append((1 << top) - 1)          # rounds up into the exponent
        low = top - 54                       # bits below the rounding bit
        head = rnd.getrandbits(52) | (1 << 52)
        for odd in (0, 1):                   # exact ties, both ways
            vals.append((((head & ~1) | odd) << 1 | 1) << low)
        if low:                              # a sticky bit breaks the tie
            vals.append(((((head & ~1) << 1) | 1) << low) | 1)
    return vals


@pytest.mark.parametrize("width", [1, 52, 53, 54, 63, 64, 65, 105, 106])
def test_float64_bits_equal_float_of_int(width):
    rnd = random.Random(width)
    vals = _field_values(rnd, width)
    want = np.asarray([float(v) for v in vals])
    # the field at offsets across a word and a limb, between random bits
    for off in (0, 5, 31, 32, 60, 200, 1020 - width):
        x = limbs.from_pyints(_embedded(rnd, vals, off, width), 128)
        bits = encoding.fields_float64_bits(
            x, np.int32([off, 0]), np.int32([width, 0]))
        got = encoding.float64_of_bits(bits)
        assert np.array_equal(got[0], want), off
        assert np.array_equal(got[1], np.zeros(len(vals)))


def _mo_candidates(rnd, mo, M, L):
    """M candidates' n_k plaintexts as (M * n_k, L) limbs, each class slot
    ``g << b_h | h`` with random fields, the first class of a ciphertext
    most significant."""
    base = mo.base
    ints = []
    for _ in range(M):
        for k in range(mo.n_k):
            used = mo.slots_in_ct(k)
            slots = [(rnd.getrandbits(base.b_g) << base.b_h)
                     | rnd.getrandbits(base.b_h) for _ in range(used)]
            ints.append(sum(v << ((used - 1 - s) * base.b_gh)
                            for s, v in enumerate(slots)))
    return limbs.from_pyints(ints, L)


def _mo_codec(n_classes, key_bits, b_g=67, b_h=62):
    # b_gh 129 bits: slots start off the 8-bit limbs and 32-bit words
    base = encoding.PackingPlan(r=53, g_off=0.731, b_g=b_g, b_h=b_h,
                                n_capacity=N_CAPACITY,
                                plaintext_bits=key_bits - 1)
    return MOCodec(mo_encoding.MOPackingPlan(base=base, n_classes=n_classes))


def _decodes(codec, plain, counts):
    """(device, host words, python ints) decodes of the same plaintexts."""
    return (codec.decode_device(jnp.asarray(plain), counts),
            codec.decode_limbs(plain, counts, None),
            _decode_pyints(codec, plain, counts, None, True))


@pytest.mark.parametrize("key_bits,L", [(512, 67), (1024, 128)],
                         ids=["512-hist-width", "1024"])
@pytest.mark.parametrize("n_classes", [3, 10, 11])
def test_mo_device_decode_equals_host_and_pyints(n_classes, key_bits, L):
    """At 512 bits the plaintexts keep the histogram width (67 limbs, as
    the plain cipher leaves them), not a whole number of words."""
    rnd = random.Random(f"{n_classes}-{key_bits}")
    codec = _mo_codec(n_classes, key_bits)
    mo = codec.plan
    assert (mo.eta_c, mo.n_k) == {512: (3, -(-n_classes // 3)),
                                  1024: (7, -(-n_classes // 7))}[key_bits]
    M = 37
    plain = _mo_candidates(rnd, mo, M, L)
    counts = np.asarray([N_CAPACITY, 1] + [rnd.randrange(1, N_CAPACITY + 1)
                                           for _ in range(M - 2)])
    device, words, pyints = _decodes(codec, plain, counts)
    for d, w, p in zip(device, words, pyints):
        assert d.shape == (M, n_classes)
        assert np.array_equal(d, w)
        assert np.array_equal(d, p)


def test_mo_device_decode_in_row_blocks(monkeypatch):
    """Past ``limbs.ROW_BLOCK`` plaintexts the decode runs block by block,
    ``ROW_BLOCK // n_k`` candidates a block, the last zero-padded: the
    same sums."""
    rnd = random.Random(11)
    codec = _mo_codec(10, 1024)
    plain = _mo_candidates(rnd, codec.plan, 29, 128)
    counts = np.asarray([rnd.randrange(1, N_CAPACITY + 1)
                         for _ in range(29)])
    whole = codec.decode_device(jnp.asarray(plain), counts)
    monkeypatch.setattr(limbs, "ROW_BLOCK", 16)      # 8 candidates a block
    blocked = codec.decode_device(jnp.asarray(plain), counts)
    words = codec.decode_limbs(plain, counts, None)
    for b, w, h in zip(blocked, whole, words):
        assert np.array_equal(b, w) and np.array_equal(b, h)


def test_mo_device_decode_declines_fields_past_106_bits():
    for b_g, b_h in [(107, 60), (60, 107)]:
        codec = _mo_codec(3, 1024, b_g, b_h)
        plain = jnp.zeros((3 * codec.n_slots, 128), jnp.int32)
        assert codec.decode_device(plain, np.ones(3)) is None


def test_wider_g_field_reuses_the_executable():
    """``b_g`` grows a bit when a round's g range passes a power of two:
    offsets and widths are traced, so the next decode builds nothing."""
    rnd = random.Random(5)
    built, armed = [], [False]

    def on_event(event, secs, **kw):
        if armed[0] and event == "/jax/core/compile/backend_compile_duration":
            built.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    counts = np.arange(1, 22)
    for b_g in (65, 66, 67):
        codec = _mo_codec(10, 1024, b_g=b_g, b_h=63)
        plain = _mo_candidates(rnd, codec.plan, 21, 128)
        armed[0] = b_g > 65
        try:
            got = codec.decode_device(jnp.asarray(plain), counts)
        finally:
            armed[0] = False
        want = codec.decode_limbs(plain, counts, None)
        assert all(np.array_equal(a, b) for a, b in zip(got, want))
    assert built == []


def _mo_fit(**kw):
    rng = np.random.default_rng(3)
    X = rng.normal(0, 1, (240, 7)).astype(np.float32)
    W = rng.normal(0, 1, (7, 4))
    y = np.argmax(X @ W + 0.5 * rng.normal(0, 1, (240, 4)),
                  axis=1).astype(np.float64)
    params = SBTParams(objective="mo", n_classes=4, cipher="affine",
                       key_bits=256, n_trees=1, max_depth=3, n_bins=16,
                       seed=2, **kw)
    return VerticalBoosting(params).fit(X[:, :3], y, [X[:, 3:]])


def test_mo_tree_is_the_same_with_the_host_words_path_forced(monkeypatch):
    device = _mo_fit()
    monkeypatch.setattr(MOCodec, "decode_device", None)
    host = _mo_fit()
    assert device.trees[0].signature() == host.trees[0].signature()
    d, h = device.stats, host.stats
    assert d.n_decode_device == h.n_decode_words == d.n_split_infos > 0
    assert d.n_decode_words == h.n_decode_device == 0
    assert d.n_decrypt == h.n_decrypt


@pytest.mark.parametrize("objective,path", [("mo", "device"),
                                            ("binary", "words")])
def test_decode_counter_and_span_path(objective, path):
    """``Stats.n_decode_device`` counts the candidates the MO guest decodes
    on the device, and every ``decode`` span says which path ran; binary
    candidates keep the host words path."""
    if objective == "mo":
        model = _mo_fit(trace=True)
    else:
        X = np.random.default_rng(0).standard_normal((200, 4))
        X = X.astype(np.float32)
        model = VerticalBoosting(SBTParams(
            n_trees=1, max_depth=2, n_bins=8, cipher="affine", key_bits=256,
            trace=True)).fit(X[:, :2], (X[:, 0] > 0) * 1.0, [X[:, 2:]])
    s = model.stats
    assert getattr(s, f"n_decode_{path}") == s.n_split_infos > 0
    assert s.n_decode_device + s.n_decode_words == s.n_split_infos
    spans = [e[6] for e in model.tracer.export_events()
             if e[0] == "X" and e[1] == "decode"]
    assert spans and {a["path"] for a in spans} == {path}


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs "
                    "XLA_FLAGS=--xla_force_host_platform_device_count>=2")
def test_mesh_decrypt_feeds_the_device_decode():
    """On a (data, model) mesh a layer of 1,024 or more candidate
    ciphertexts decrypts sharded; its plaintexts go on to the same device
    decode, and the tree is the one the single device grows."""
    from repro.launch.mesh import make_gbdt_mesh
    rng = np.random.default_rng(4)
    X = rng.normal(0, 1, (300, 24)).astype(np.float32)
    y = np.argmax(X[:, :4] + 0.5 * rng.normal(0, 1, (300, 4)),
                  axis=1).astype(np.float64)
    kw = dict(objective="mo", n_classes=4, cipher="affine", key_bits=256,
              n_trees=1, max_depth=2, n_bins=16, seed=2)
    one = VerticalBoosting(SBTParams(**kw)).fit(X[:, :4], y, [X[:, 4:]])
    mesh = VerticalBoosting(SBTParams(**kw, mesh=make_gbdt_mesh())).fit(
        X[:, :4], y, [X[:, 4:]])
    assert mesh.trees[0].signature() == one.trees[0].signature()
    assert mesh.stats.n_decode_device == mesh.stats.n_split_infos > 0
    # 20 host features x 15 thresholds x n_k 2 a node: the second layer
    # passes the sharded decrypt's 256 rows a data shard
    assert mesh.stats.n_decrypt >= 3 * 600
