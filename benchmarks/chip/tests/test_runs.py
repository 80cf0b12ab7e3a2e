"""Whole runs of tiny cells on the CPU, the chip check skipped: a sound
program proves correct, and the control and every fault a cell can have
come out as not correct.

The faults are planted in the program where the timed path produces its
answer: a round or a batch whose state comes back unchanged, half of the
rows left out (the rest's mean standing in for them), and one answer
altered.  The cells run on one chip, so no exchange between chips can be
left out."""

import numpy as np
import pytest

from conftest import run_tiny


def test_sound_runs_are_correct(tiny_bench):
    for cell in [w["name"] for w in tiny_bench.spec["workloads"]]:
        out = run_tiny(tiny_bench, cell)
        assert out["correct"], (cell, out["checks"])
        assert out["attempted"] > 0 and out["failed"] == 0
        assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", ["higgs.train", "higgs.serve"])
def test_float32_control_is_not_correct(tiny_bench, cell):
    out = run_tiny(tiny_bench, cell, candidates=("float32",))
    assert not out["correct"], out["checks"]


def _train_fault(monkeypatch, fault):
    from repro.core.boosting import VerticalBoosting
    from repro.core.loss import LogLoss
    if fault == "state_unchanged":
        monkeypatch.setattr(VerticalBoosting, "boost_round",
                            lambda self, t, score: np.array(score))
    elif fault == "half_rows":
        grad_hess = LogLoss.grad_hess

        def half(y, score):
            g, h = grad_hess(y, score)
            g, h = g.copy(), h.copy()
            g[len(g) // 2:] = g[:len(g) // 2].mean()
            h[len(h) // 2:] = h[:len(h) // 2].mean()
            return g, h
        monkeypatch.setattr(LogLoss, "grad_hess", staticmethod(half))
    elif fault == "answer_altered":
        apply = VerticalBoosting._apply

        def altered(self, score, tree, leaf_rows, cls=-1):
            apply(self, score, tree, leaf_rows, cls)
            score[0] += 1e-6
        monkeypatch.setattr(VerticalBoosting, "_apply", altered)


def _serve_fault(monkeypatch, fault):
    from repro.core.boosting import VerticalBoosting
    proba = VerticalBoosting.predict_proba
    first = {}

    def faulty(self, X_guest, X_hosts, packed=True):
        p = np.array(proba(self, X_guest, X_hosts, packed))
        if fault == "state_unchanged":
            return first.setdefault("p", p)
        if fault == "half_rows":
            p[len(p) // 2:] = p[:len(p) // 2].mean()
        elif fault == "answer_altered":
            p[0] += 1e-9
        return p
    monkeypatch.setattr(VerticalBoosting, "predict_proba", faulty)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_rows",
                                   "answer_altered"])
@pytest.mark.parametrize("cell", ["higgs.train", "higgs.serve"])
def test_faults_are_not_correct(tiny_bench, monkeypatch, cell, fault):
    plant = _train_fault if cell.endswith("train") else _serve_fault
    plant(monkeypatch, fault)
    out = run_tiny(tiny_bench, cell)
    assert not out["correct"], (fault, out["checks"])
