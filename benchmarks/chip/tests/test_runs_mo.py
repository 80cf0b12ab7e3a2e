"""The ``svhn.train_mo`` cell at a tiny size on the CPU, the chip check
skipped: the float32 control and every fault a multi-output round can have
come out as not correct; and ``costs_mo`` by hand.

The faults are planted where the timed path produces its answer: a round
whose score comes back unchanged, half of the rows' (n, 10) gradients
replaced by the other half's mean (through ``SoftmaxLoss.grad_hess``),
and one answer altered."""

import numpy as np
import pytest

import costs
import costs_mo
from conftest import run_tiny

CELL = "svhn.train_mo"


def test_float32_control_is_not_correct(tiny_bench):
    out = run_tiny(tiny_bench, CELL, candidates=("float32",))
    assert not out["correct"], out["checks"]


def _plant(monkeypatch, fault):
    from repro.core.boosting import VerticalBoosting
    from repro.core.loss import SoftmaxLoss
    if fault == "state_unchanged":
        monkeypatch.setattr(VerticalBoosting, "boost_round",
                            lambda self, t, score: np.array(score))
    elif fault == "half_rows":
        grad_hess = SoftmaxLoss.grad_hess

        def half(self, y, score):
            g, h = grad_hess(self, y, score)
            g, h = g.copy(), h.copy()
            g[len(g) // 2:] = g[:len(g) // 2].mean(axis=0)
            h[len(h) // 2:] = h[:len(h) // 2].mean(axis=0)
            return g, h
        monkeypatch.setattr(SoftmaxLoss, "grad_hess", half)
    elif fault == "answer_altered":
        apply = VerticalBoosting._apply

        def altered(self, score, tree, leaf_rows, cls=-1):
            apply(self, score, tree, leaf_rows, cls)
            score[0, 0] += 1e-6
        monkeypatch.setattr(VerticalBoosting, "_apply", altered)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_rows",
                                   "answer_altered"])
def test_faults_are_not_correct(tiny_bench, monkeypatch, fault):
    _plant(monkeypatch, fault)
    out = run_tiny(tiny_bench, CELL)
    assert not out["correct"], (fault, out["checks"])


def test_round_records_the_ciphertexts_decrypted(tiny_bench):
    """Each unit's ``decrypt_cts`` is what the metric averages: n_fh x
    (n_b - 1) candidates a split node, n_k ciphertexts each (tiny cell:
    4 host features, 16 bins, 256-bit key, so eta_c 2 and n_k 5)."""
    import harness
    captured = {}
    window = harness._window

    def keep(run, kind, state, starts):
        captured["run"] = run
        window(run, kind, state, starts)
    try:
        harness._window = keep
        run_tiny(tiny_bench, CELL)
    finally:
        harness._window = window
    run = captured["run"]
    per_node = 4 * 15 * 5
    assert all(u["decrypt_cts"] > 0 and u["decrypt_cts"] % per_node == 0
               and u["decrypt_cts"] <= per_node * (1 + 2 + 4)
               for u in run.units)
    read = tiny_bench.reader("decrypt_cts.train").read(run)
    assert read == np.mean([u["decrypt_cts"] for u in run.units])


@pytest.mark.parametrize("rows,eta_c,n_k", [(12411, 7, 2), (8, 8, 2)])
def test_costs_mo_decrypts_every_slot_of_every_candidate(rows, eta_c, n_k):
    # 1,536 host features, depth 5, 32 bins, 1024-bit keys, 10 classes;
    # b_gh at r = 53: 68 + 67 = 135 bits at 12,411 rows, 58 + 57 = 115 at 8
    w = costs_mo.train_round(rows, 1536, 1536, 5, 32, 1024, 10)
    b_gh = costs.packing_width(rows)
    assert w["eta_c"] == 1023 // b_gh == eta_c
    assert w["n_k"] == -(-10 // eta_c) == n_k
    assert w["decrypt"] == 32 * 1536 * 32 * n_k
    assert w["encrypt"] == rows * n_k


@pytest.mark.parametrize("shape", [(8, 1, 2, 2, 4, 64),
                                   (12411, 1536, 1536, 5, 32, 1024)])
def test_costs_mo_at_one_class_is_the_binary_round_but_decryption(shape):
    mo = costs_mo.train_round(*shape, n_classes=1)
    binary = costs.train_round(*shape)
    for key in ("hom_add", "encrypt", "guest_add"):
        assert mo[key] == binary[key], key
    assert mo["n_k"] == 1
    n_rows, _, n_fh, h, n_b, _ = shape
    assert mo["decrypt"] == n_b * n_fh * 2 ** h      # no compression
    # operations and bytes differ from the binary round's by the
    # decryptions alone
    k = shape[5] // 8
    extra = mo["decrypt"] - binary["decrypt"]
    assert mo["ops"] == pytest.approx(binary["ops"] + extra * 6 * k * k)
    assert mo["bytes"] == pytest.approx(binary["bytes"] + 2 * extra * k)
