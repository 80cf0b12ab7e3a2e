"""SecureBoost-MO against the benchmark's plain reference.

``VerticalBoosting(objective="mo")`` grows one multi-output tree a round,
the classes' g/h packed ``eta_c`` to a ciphertext.  Each round's (n, l)
score update must equal what ``benchmarks/chip/reference_mo.py`` (numpy,
written from the paper, importing nothing of the program) grows from the
same input score, within the ``svhn.train_mo`` cell's ``round_gap`` limit.
At a 256-bit key ``eta_c`` is 2: 10 classes fill five ciphertexts, 3
classes leave the second one half empty.
"""

import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import SBTParams, VerticalBoosting

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
LIMIT = json.loads((CHIP / "limits" / "svhn.train_mo.json")
                   .read_text())["round_gap"]


def _load(name: str):
    """A benchmark module by path; ``reference_mo`` imports ``reference``
    by name, so both are registered under their own names."""
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name,
                                                      CHIP / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


reference = _load("reference")
reference_mo = _load("reference_mo")


def _data(n_classes: int, n: int = 300, seed: int = 5):
    rng = np.random.default_rng(seed)
    Xg = rng.standard_normal((n, 3)).astype(np.float32)
    Xh = rng.standard_normal((n, 4)).astype(np.float32)
    W = rng.normal(0.0, 1.0, (7, n_classes))
    s = np.hstack([Xg, Xh]) @ W + 0.5 * rng.normal(0.0, 1.0, (n, n_classes))
    return Xg, Xh, np.argmax(s, axis=1).astype(np.float64)


def _params(n_classes: int, cipher: str, **kw) -> SBTParams:
    return SBTParams(objective="mo", n_classes=n_classes, cipher=cipher,
                     key_bits=256, max_depth=3, n_bins=16, **kw)


def _rounds(model, Xg, Xh, y, n_rounds: int = 3) -> list:
    """[(input score, output score)] of the first rounds."""
    score = model.begin_fit(Xg, y, [Xh])
    out = []
    for t in range(n_rounds):
        nxt = model.boost_round(t, score)
        out.append((score, nxt))
        score = nxt
    return out


def _ref(model):
    p = model.params
    return {k: getattr(p, k) for k in ("max_depth", "n_bins",
                                       "learning_rate", "lam", "min_leaf",
                                       "min_gain")}


@pytest.mark.parametrize("n_classes,n_k", [(3, 2), (10, 5)])
@pytest.mark.parametrize("cipher", ["plain", "affine"])
def test_mo_rounds_match_the_reference(cipher, n_classes, n_k):
    Xg, Xh, y = _data(n_classes)
    model = VerticalBoosting(_params(n_classes, cipher))
    rounds = _rounds(model, Xg, Xh, y)
    p = _ref(model)
    _, bins_t = reference.bin_parties([Xg, Xh], p["n_bins"])
    for s_in, s_out in rounds:
        g, h = reference_mo.softmax_grad(y, s_in)
        _, want, open_rows = reference_mo.grow_tree(bins_t, g, h, p)
        assert reference_mo.update_gap(s_out - s_in, want, open_rows) \
            <= LIMIT
    # the packing under test: eta_c classes a ciphertext, n_k a row
    plan = model._make_codec(model.cipher, g, h).plan
    assert (plan.eta_c, plan.n_k) == (2, n_k)
    assert model.stats.n_encrypt == 3 * len(y) * n_k


def test_float32_reference_fails_the_limit():
    """The control one precision below float64 reads above the limit, so
    the limit can tell a lower-precision learner from the program."""
    Xg, Xh, y = _data(10)
    model = VerticalBoosting(_params(10, "plain"))
    p = _ref(model)
    _, bins_t = reference.bin_parties([Xg, Xh], p["n_bins"])
    worst = 0.0
    for s_in, _ in _rounds(model, Xg, Xh, y):
        g, h = reference_mo.softmax_grad(y, s_in)
        _, want, open_rows = reference_mo.grow_tree(bins_t, g, h, p)
        g32, h32 = reference_mo.softmax_grad(y, s_in, np.float32)
        got = reference_mo.grow_tree(bins_t, g32, h32, p, np.float32)[1]
        worst = max(worst, reference_mo.update_gap(got, want, open_rows))
    assert worst > LIMIT


def test_spans_show_the_packing_and_the_ciphertexts_decrypted():
    """``encode`` and ``decode`` carry the plan's ``n_classes``, ``eta_c``
    and ``n_k``; the ``decrypt`` spans' ``cts`` add up to
    ``Stats.n_decrypt``: every candidate's n_k ciphertexts, uncompressed."""
    Xg, Xh, y = _data(3)
    model = VerticalBoosting(_params(3, "affine", trace=True))
    _rounds(model, Xg, Xh, y, n_rounds=1)
    ev = [e for e in model.tracer.export_events() if e[0] == "X"]
    want = {"n_classes": 3, "eta_c": 2, "n_k": 2}
    for name in ("encode", "decode"):
        spans = [e for e in ev if e[1] == name]
        assert spans and all({k: e[6].get(k) for k in want} == want
                             for e in spans), name
    decrypts = [e[6] for e in ev if e[1] == "decrypt"]
    assert sum(a["cts"] for a in decrypts) == model.stats.n_decrypt > 0
    # n_fh x (n_b - 1) candidates per split node, n_k ciphertexts each
    nodes = sum(a["nodes"] for a in decrypts)
    assert model.stats.n_decrypt == 4 * 15 * 2 * nodes


def test_binary_spans_carry_no_packing_attributes():
    X = np.random.default_rng(0).standard_normal((200, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    model = VerticalBoosting(SBTParams(n_trees=1, max_depth=2, n_bins=8,
                                       cipher="affine", key_bits=256,
                                       trace=True))
    model.fit(X[:, :2], y, [X[:, 2:]])
    ev = [e for e in model.tracer.export_events() if e[0] == "X"]
    for e in ev:
        if e[1] in ("encode", "decode"):
            assert not {"n_classes", "eta_c", "n_k"} & set(e[6])
    assert sum(e[6]["cts"] for e in ev if e[1] == "decrypt") \
        == model.stats.n_decrypt


def test_no_executable_is_built_when_a_round_widens_the_packing():
    """A round whose g range grows past 1.0 widens ``b_g`` by a bit (here
    124 -> 125 bits a slot at 1,023 rows).  The packed plaintext keeps the
    cipher's width, so the encryption sees the same shape and nothing
    compiles after the first round."""
    import jax
    Xg, Xh, y = _data(3, n=1023)
    model = VerticalBoosting(SBTParams(objective="mo", n_classes=3,
                                       cipher="affine", key_bits=512,
                                       max_depth=3, n_bins=16))
    built, armed = [], [False]

    def on_event(event, secs, **kw):
        if armed[0] and event == "/jax/core/compile/backend_compile_duration":
            built.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    score = model.begin_fit(Xg, y, [Xh])
    widths = []
    try:
        for t in range(3):
            plan = model._make_codec(model.cipher, *model._loss.grad_hess(
                model._y, score)).plan
            widths.append((plan.base.b_gh, plan.limb_width))
            armed[0] = t > 0
            score = model.boost_round(t, score)
    finally:
        armed[0] = False
    assert widths[0][0] < widths[1][0]               # the packing widened
    assert {w for _, w in widths} == {64}            # 511 bits, as limbs
    assert built == []


def test_limb_work_in_row_blocks_is_bit_identical():
    """``limbs.by_row_blocks`` runs a row-wise limb chain over blocks, the
    last zero-padded: the same limbs as one call, for lazy mixed-sign
    accumulators through the affine reduce and for the decryption."""
    import jax.numpy as jnp

    from repro.core.he import get_cipher, limbs
    from repro.kernels.modmul import decrypt_batch
    cipher = get_cipher("affine", key_bits=256, seed=3)
    rng = np.random.default_rng(7)
    lazy = rng.integers(-300, 3000, (5, 3, 2, cipher.hist_width))
    lazy[..., -4:] = 0                      # within the Barrett bound
    lazy[..., -5] = 1                       # and positive
    lazy = jnp.asarray(lazy, jnp.int32)
    whole = np.asarray(cipher.reduce(lazy))
    for block in (4, 7, 30, 64):
        got = limbs.by_row_blocks(
            lambda a: limbs.barrett_reduce(limbs.carry_fix(a), cipher.bctx),
            lazy, block)
        assert np.array_equal(np.asarray(got), whole), block
    cts = jnp.asarray(whole.reshape(-1, whole.shape[-1]))
    plain = np.asarray(decrypt_batch(cipher, cts))
    got = limbs.by_row_blocks(lambda a: decrypt_batch(cipher, a), cts, 8)
    assert np.array_equal(np.asarray(got), plain)


def test_mo_trees_do_not_change_when_limb_work_runs_in_row_blocks(
        monkeypatch):
    """An MO layer's reduce and decryption past ``limbs.ROW_BLOCK`` rows
    run block by block (an SVHN-width layer holds 1.57M candidate rows):
    the trees and scores are bit-identical to one call per layer."""
    from repro.core.he import limbs
    Xg, Xh, y = _data(3, n=200)

    def fit():
        model = VerticalBoosting(_params(3, "affine"))
        rounds = _rounds(model, Xg, Xh, y, n_rounds=2)
        return model, rounds[-1][1]
    whole, s_whole = fit()
    monkeypatch.setattr(limbs, "ROW_BLOCK", 96)
    blocked, s_blocked = fit()
    assert [t.signature() for t in blocked.trees] \
        == [t.signature() for t in whole.trees]
    assert np.array_equal(s_blocked, s_whole)
    # every layer's candidates (4 x 15 x 2 cts a node) and histograms
    # (4 x 16 x 2 a node) pass the 96-row block
    assert blocked.stats.n_decrypt == whole.stats.n_decrypt > 2 * 96
