"""Traffic kind ``train_mo``: SecureBoost-MO rounds, back to back.

As ``train_rounds``, whose set-up, unit and window it shares, with a
multi-class task: the features are seeded as ``tabular.vertical`` seeds
them, and the label is one of ``n_classes`` roughly balanced classes, the
argmax of as many seeded linear scores over every feature plus noise,
drawn on a stream of its own.  Set-up runs ``begin_fit`` and the warm-up
rounds through ``boost_round``; one unit is one round (one multi-output
tree), and its record adds ``decrypt_cts``, the ciphertexts the guest
decrypted in it (``Stats.n_decrypt``).

The check grows ``check_rounds`` window rounds drawn from the seed again
with ``reference_mo`` from the program's input score: ``round_gap`` is the
largest difference between the two (n, n_classes) score updates over the
rows and classes, as a share of the reference update's largest magnitude,
the worst over the checked rounds.  Rows of a node where candidates tie
(``reference``'s rule) are left out, and their number is printed.
"""

from __future__ import annotations

import gc
import sys
from pathlib import Path

import numpy as np

import harness
import reference
import reference_mo
import tabular

train_rounds = harness.load_module(Path(__file__).with_name(
    "train_rounds.py"))
SPAN = train_rounds.SPAN

NOISE = 0.5     # label noise, as a share of a class score's spread


def labels(Xg: np.ndarray, Xh: np.ndarray, n_classes: int,
           seed: int) -> np.ndarray:
    """Float class ids 0 .. n_classes - 1: the argmax of ``n_classes``
    linear scores, each weight column of unit norm (so no class's score
    spreads wider than another's), plus noise."""
    rng = tabular.rng_for(seed, 11)
    n_f = Xg.shape[1] + Xh.shape[1]
    W = rng.normal(0.0, 1.0, (n_f, n_classes))
    W = (W / np.linalg.norm(W, axis=0)).astype(np.float32)
    s = Xg @ W[:Xg.shape[1]] + Xh @ W[Xg.shape[1]:]
    s = s.astype(np.float64) + NOISE * rng.normal(0.0, 1.0, s.shape)
    return np.argmax(s, axis=1).astype(np.float64)


def setup(run) -> train_rounds.State:
    from repro.core import SBTParams, VerticalBoosting
    data, params = run.config["data"], run.config["params"]
    Xg, Xh, _ = tabular.vertical(data["rows"], data["guest_features"],
                                 data["host_features"], run.seed)
    y = labels(Xg, Xh, params["n_classes"], run.seed)
    model = VerticalBoosting(SBTParams(**train_rounds.sbt_params(run)))
    score = model.begin_fit(Xg, y, [Xh])
    for t in range(run.traffic["warmup_rounds"]):
        score = model.boost_round(t, score)
    return train_rounds.State(Xg=Xg, Xh=Xh, y=y, model=model,
                              scores=[score],
                              next_round=run.traffic["warmup_rounds"])


def step(run, state: train_rounds.State, i: int) -> dict:
    before = state.model.stats.n_decrypt
    record = train_rounds.step(run, state, i)
    record["decrypt_cts"] = state.model.stats.n_decrypt - before
    return record


def check(run, state: train_rounds.State, candidates) -> dict:
    """``{candidate: {"round_gap": value}}``.  Candidate "program" is the
    program's update; "float32" puts the reference, computed in float32,
    in the program's place."""
    n_rounds = len(state.scores) - 1
    k = min(run.traffic["check_rounds"], n_rounds)
    rng = tabular.rng_for(run.seed, 7)
    rounds = sorted(int(r) for r in rng.choice(n_rounds, k, replace=False))
    state.model = None                 # free the program's device state
    gc.collect()
    p = train_rounds.reference_params(run)
    _, bins_t = reference.bin_parties([state.Xg, state.Xh], p["n_bins"])
    gaps = {c: 0.0 for c in candidates}
    left_out = 0
    for r in rounds:
        s_in, s_out = state.scores[r], state.scores[r + 1]
        g, h = reference_mo.softmax_grad(state.y, s_in)
        _, want, open_rows = reference_mo.grow_tree(bins_t, g, h, p)
        left_out += int(open_rows.sum())
        for c in candidates:
            if c == "program":
                got = s_out - s_in
            else:
                g32, h32 = reference_mo.softmax_grad(state.y, s_in,
                                                     np.float32)
                got = reference_mo.grow_tree(bins_t, g32, h32, p,
                                             np.float32)[1]
            gaps[c] = max(gaps[c],
                          reference_mo.update_gap(got, want, open_rows))
    print(f"train check: rounds {rounds} of {n_rounds}, {left_out} rows "
          f"left out as tie-open", file=sys.stderr, flush=True)
    return {c: {"round_gap": gaps[c]} for c in candidates}
