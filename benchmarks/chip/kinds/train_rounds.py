"""Traffic kind ``train_rounds``: federated boosting rounds, back to back.

Set-up makes the configuration's data from the seed, runs
``VerticalBoosting.begin_fit`` (binning, cipher keys) and the traffic's
warm-up rounds through ``boost_round``, the window's own call, so that
every shape a round uses is compiled before the window opens.  The window
then calls ``boost_round`` again and again: one unit is one round.

The check takes rounds of the window drawn from the seed
(``check_rounds`` of them) and grows each again with the plain reference
from the same input score: the round's score update, which carries its
split choices, leaf weights and row routing, must equal the reference's.
The number compared, ``round_gap``, is the largest difference between the
two updates over the rows, as a share of the reference update's largest
magnitude, the worst over the checked rounds.  Rows of a node where
candidates tie (see ``reference``) are left out, and their number is
printed.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import time

import numpy as np

import reference
import tabular

SPAN = "round"


@dataclasses.dataclass
class State:
    Xg: np.ndarray
    Xh: np.ndarray
    y: np.ndarray
    model: object
    scores: list          # scores[k]: input of window round k
    next_round: int


def sbt_params(run) -> dict:
    """The configuration's ``SBTParams``, as the file states them."""
    return dict(run.config["params"])


def setup(run) -> State:
    from repro.core import SBTParams, VerticalBoosting
    data = run.config["data"]
    Xg, Xh, y = tabular.vertical(data["rows"], data["guest_features"],
                                 data["host_features"], run.seed)
    model = VerticalBoosting(SBTParams(**sbt_params(run)))
    score = model.begin_fit(Xg, y, [Xh])
    for t in range(run.traffic["warmup_rounds"]):
        score = model.boost_round(t, score)
    return State(Xg=Xg, Xh=Xh, y=y, model=model, scores=[score],
                 next_round=run.traffic["warmup_rounds"])


def _counters(model) -> dict:
    st = model.stats
    return {"encrypt_s": st.encrypt_seconds,
            "guest_hist_s": st.guest_hist_seconds,
            "host_wait_s": st.host_wait_seconds,
            "wire_bytes": model.channel.total_bytes}


def step(run, state: State, i: int) -> dict:
    before = _counters(state.model)
    t0 = time.perf_counter()
    score = state.model.boost_round(state.next_round, state.scores[-1])
    seconds = time.perf_counter() - t0
    after = _counters(state.model)
    state.next_round += 1
    state.scores.append(score)
    return {"seconds": seconds,
            **{k: after[k] - before[k] for k in after}}


def reference_params(run) -> dict:
    p = sbt_params(run)
    keys = ("max_depth", "n_bins", "learning_rate", "lam", "min_leaf",
            "min_gain")
    return {k: p[k] for k in keys}


def check(run, state: State, candidates) -> dict:
    """``{candidate: {"round_gap": value}}``.  Candidate "program" is the
    program's update; "float32" puts the reference, computed in float32, in
    the program's place."""
    n_rounds = len(state.scores) - 1
    k = min(run.traffic["check_rounds"], n_rounds)
    rng = tabular.rng_for(run.seed, 7)
    rounds = sorted(int(r) for r in rng.choice(n_rounds, k, replace=False))
    state.model = None                 # free the program's device state
    gc.collect()
    p = reference_params(run)
    _, bins_t = reference.bin_parties([state.Xg, state.Xh], p["n_bins"])
    gaps = {c: 0.0 for c in candidates}
    left_out = 0
    for r in rounds:
        s_in, s_out = state.scores[r], state.scores[r + 1]
        g, h = reference.log_loss_grad(state.y, s_in)
        _, want, open_rows = reference.grow_tree(bins_t, g, h, p)
        keep = ~open_rows
        left_out += int(open_rows.sum())
        scale = max(float(np.abs(want).max()), np.finfo(np.float64).tiny)
        for c in candidates:
            if c == "program":
                got = s_out - s_in
            else:
                g32, h32 = reference.log_loss_grad(state.y, s_in, np.float32)
                got = reference.grow_tree(bins_t, g32, h32, p, np.float32)[1]
            diff = np.abs(got - want)[keep]
            gap = float(diff.max()) / scale if diff.size else 0.0
            gaps[c] = max(gaps[c], gap if gap == gap else np.inf)
    print(f"train check: rounds {rounds} of {n_rounds}, {left_out} rows "
          f"left out as tie-open", file=sys.stderr, flush=True)
    return {c: {"round_gap": gaps[c]} for c in candidates}
