"""The guest's word decode (``codec.decode_limbs``) against the python-int
path (``tree._decode_pyints``): bit for bit, for every codec, and which
path a federated fit takes."""

import random

import numpy as np
import pytest

from repro.core import SBTParams, VerticalBoosting, encoding, mo_encoding
from repro.core.he import limbs
from repro.core.tree import MOCodec, NoPackCodec, PackedCodec, _decode_pyints

L = 131                       # a 1024-bit package with the histogram headroom


def _values(rnd, bits, n, kind):
    """``n`` field values of ``bits`` bits of one kind."""
    if kind == "ones":
        return [(1 << bits) - 1] * n
    if kind == "zeros":
        return [0] * n
    if kind == "ties":
        # 54 significant bits ending in 1: halfway between two floats, the
        # 53-bit head odd or even, so rounding goes both ways; plus the
        # ties just above 2**53
        out = [(1 << 53) + 1, (1 << 53) + 3]
        while len(out) < n:
            head = rnd.getrandbits(52) | (1 << 52)
            out.append(((head << 1) | 1) << rnd.randrange(bits - 53))
        return out[:n]
    return [rnd.getrandbits(bits) for _ in range(n)]


def _slots(rnd, plan, n, kind):
    """``n`` packed (g, h) slots: ``g << b_h | h``."""
    gs = _values(rnd, plan.b_g, n, kind)
    hs = _values(rnd, plan.b_h, n, kind)
    rnd.shuffle(hs)
    return [(g << plan.b_h) | h for g, h in zip(gs, hs)]


def _counts(rnd, n, kind, n_rows=12_500):
    if kind == "one":
        return np.ones(n, np.int64)
    if kind == "all":
        return np.full(n, n_rows, np.int64)
    return np.asarray([rnd.randrange(1, n_rows + 1) for _ in range(n)])


def _plan(b_g, b_h, g_off):
    return encoding.PackingPlan(r=53, g_off=g_off, b_g=b_g, b_h=b_h,
                                n_capacity=12_500, plaintext_bits=1023)


def _packed_compressed(rnd, plan, M, kind):
    """M candidates in packages of eta_s slots, the first most
    significant, the last group short and zero-padded (limb backends)."""
    codec = PackedCodec(plan)
    eta, b = codec.eta_s, codec.b_slot
    vals = _slots(rnd, plan, M, kind)
    vals += [0] * (-M % eta)
    pkgs = [sum(v << ((eta - 1 - s) * b) for s, v in
                enumerate(vals[i:i + eta])) for i in range(0, len(vals), eta)]
    sizes = np.full(len(pkgs), eta, np.int64)
    sizes[-1] = M - eta * (len(pkgs) - 1)
    return codec, limbs.from_pyints(pkgs, L), sizes


def _packed_uncompressed(rnd, plan, M, kind):
    return (PackedCodec(plan),
            limbs.from_pyints(_slots(rnd, plan, M, kind), L), None)


def _nopack(rnd, plan, M, kind):
    codec = NoPackCodec(r=plan.r, g_off=plan.g_off)
    g = _values(rnd, plan.b_g, M, kind)
    h = _values(rnd, plan.b_h, M, kind)
    rnd.shuffle(h)
    ints = [x for pair in zip(g, h) for x in pair]     # (M, 2) row-major
    return codec, limbs.from_pyints(ints, L), None


def _mo(rnd, plan, M, kind):
    # two classes past a full ciphertext: the last one holds two slots
    n_classes = plan.compress_capacity + 2
    mo = mo_encoding.MOPackingPlan(base=plan, n_classes=n_classes)
    assert mo.n_k > 1 and mo.slots_in_ct(mo.n_k - 1) < mo.eta_c
    vals = iter(_slots(rnd, plan, M * n_classes, kind))
    ints = []
    for _ in range(M):
        for k in range(mo.n_k):
            used = mo.slots_in_ct(k)
            ints.append(sum(next(vals) << ((used - 1 - s) * plan.b_gh)
                            for s in range(used)))
    return MOCodec(mo), limbs.from_pyints(ints, L), None


LAYOUTS = {"packed-compressed": _packed_compressed,
           "packed-uncompressed": _packed_uncompressed,
           "nopack": _nopack, "mo": _mo}


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("widths,kind,g_off,counts", [
    ((68, 65), "random", 0.902, "random"),       # epsilon's packing plan
    ((68, 65), "ties", 0.5, "random"),
    ((68, 65), "ones", 1.0, "all"),
    ((68, 65), "zeros", 0.0, "one"),
    ((106, 106), "random", 0.25, "one"),         # the reader's widest field
    ((106, 106), "ties", 0.25, "all"),
    ((106, 106), "ones", 0.0, "random"),
    ((30, 20), "random", 0.0, "random"),         # fields within one piece
], ids=["eps", "eps-ties", "eps-ones", "eps-zeros", "106", "106-ties",
        "106-ones", "narrow"])
def test_word_decode_bit_identical_to_pyints(layout, widths, kind, g_off,
                                             counts):
    rnd = random.Random(f"{layout}{widths}{kind}")
    plan = _plan(*widths, g_off)
    M = 61 if layout != "mo" else 13       # 61 % eta_s != 0: a short group
    codec, plain, sizes = LAYOUTS[layout](rnd, plan, M, kind)
    cnt = _counts(rnd, M, counts)
    got = codec.decode_limbs(plain, cnt, sizes)
    assert got is not None
    want = _decode_pyints(codec, plain, cnt, sizes, True)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert np.array_equal(a, b)


def test_word_decode_reads_g_to_the_top_bit():
    """Uncompressed, ``x >> b_h`` keeps every bit above the h field: so
    does the word reader, and a value past its widest field goes back to
    python ints."""
    rnd = random.Random(7)
    plan = _plan(40, 30, 0.0)
    codec = PackedCodec(plan)
    wide = [rnd.getrandbits(100) | (1 << 99) for _ in range(9)]
    plain = limbs.from_pyints(wide, L)
    cnt = np.arange(1, 10)
    got = codec.decode_limbs(plain, cnt, None)
    want = _decode_pyints(codec, plain, cnt, None, True)
    assert all(np.array_equal(a, b) for a, b in zip(got, want))
    too_wide = limbs.from_pyints([1 << (30 + 107)], L)
    assert codec.decode_limbs(too_wide, cnt[:1], None) is None
    assert NoPackCodec(53, 0.0).decode_limbs(
        limbs.from_pyints([1 << 106, 1], L), cnt[:1], None) is None


@pytest.mark.parametrize("b_g,b_h", [(107, 60), (60, 107)])
def test_word_decode_declines_fields_past_106_bits(b_g, b_h):
    plan = _plan(b_g, b_h, 0.0)
    codec, plain, sizes = _packed_compressed(random.Random(3), plan, 10,
                                             "random")
    assert codec.decode_limbs(plain, np.ones(10), sizes) is None
    mo = MOCodec(mo_encoding.MOPackingPlan(base=plan, n_classes=3))
    assert mo.decode_limbs(np.zeros((3 * mo.n_slots, L), np.int32),
                           np.ones(3), None) is None


def _fit(**kw):
    rng = np.random.default_rng(0)
    X = rng.normal(0, 1, (160, 6)).astype(np.float32)
    w = rng.normal(0, 1, 6)
    y = (X @ w + 0.3 * rng.normal(0, 1, 160) > 0).astype(np.float64)
    if kw.get("objective") in ("mo", "multiclass"):
        y = np.digitize(X @ w, [-0.5, 0.5]).astype(np.float64)
        kw.setdefault("n_classes", 3)
    base = dict(n_trees=2, max_depth=3, n_bins=8, cipher="plain", seed=1)
    base.update(kw)
    m = VerticalBoosting(SBTParams(**base)).fit(X[:, :3], y, [X[:, 3:]])
    return m, m.predict_proba(X[:, :3], [X[:, 3:]])


@pytest.mark.parametrize("kw", [
    dict(),
    dict(compression=False),
    dict(packing=False),
    dict(objective="mo"),
    dict(objective="multiclass"),
    dict(pipeline=True),
    dict(cipher="affine", key_bits=256),
    dict(cipher="affine", key_bits=256, row_block=64),
], ids=["plain", "plain-uncompressed", "plain-nopack", "plain-mo",
        "plain-multiclass", "plain-pipeline", "affine", "affine-streamed"])
def test_limb_ciphers_decode_every_candidate_from_words(kw):
    m, _ = _fit(**kw)
    s = m.stats
    assert s.n_decode_pyints == 0
    # MO decodes its fields on the device (tests/test_decode_device.py)
    taken, other = ((s.n_decode_device, s.n_decode_words)
                    if kw.get("objective") == "mo"
                    else (s.n_decode_words, s.n_decode_device))
    assert taken == s.n_split_infos > 0
    assert other == 0


def test_paillier_decodes_through_pyints_to_the_same_model():
    """The oracle keeps the python-int path, and its exact sums grow the
    same trees as the word path's on the plain cipher."""
    kw = dict(n_trees=1, max_depth=2, precision=16)
    pai, p_pai = _fit(cipher="paillier", key_bits=256, **kw)
    pln, p_pln = _fit(**kw)
    assert pai.stats.n_decode_words == 0
    assert pai.stats.n_decode_pyints == pai.stats.n_split_infos > 0
    assert pln.stats.n_decode_pyints == 0
    assert [t.signature() for t in pai.trees] == \
        [t.signature() for t in pln.trees]
    np.testing.assert_array_equal(p_pai, p_pln)
