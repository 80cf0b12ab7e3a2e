"""Traffic kind ``serve_closed``: bulk federated scoring, closed loop.

Set-up trains the served model on the traffic's ``model`` rows and
settings through ``VerticalBoosting.begin_fit``, one ``boost_round`` per
tree and ``finish_fit``, keeping the training score after each round;
it makes a pool of batches of the configuration's width drawn from the
run's seed, and warms the batch shape up through ``predict_proba``, the
window's own call.  The window then has ``clients`` (one) caller cycling
the pool through ``predict_proba``, each call timed until its numpy array
is back: one unit is one batch.

The model's training rows come from the traffic's own ``model.seed``, not
from the run's: every run serves the same trees.  A tree's node count per
layer depends on its data (a node whose rows share one gradient stops
early), and the program compiles its training steps per node count, so
models drawn from the run's seed would compile anew in the set-up of some
runs and not others.  The run's seed draws the served rows.

The check draws ``check_batches`` of the window's calls from the seed
(reservoir sampling, so the draw does not depend on timing), grows the
plain reference's trees on the same rows, each from the training score
the program's model had before that tree, and scores the same batches
with them.  The number compared, ``proba_gap``, is the largest difference
between the served and the reference probabilities over the checked
rows; a row whose route a tie leaves open (see ``reference``) is left
out, and their number is printed.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import time

import numpy as np

import reference
import tabular

SPAN = "serve_batch"


@dataclasses.dataclass
class State:
    train: tuple          # (X_guest, X_host, y) of the served model
    scores: list          # scores[t]: training score before tree t
    pool: list            # [(X_guest, X_host)] batches
    model: object
    sample: list          # [(call index, pool index, probabilities)]
    rng: np.random.Generator


def model_params(run) -> dict:
    """The configuration's ``SBTParams`` with the traffic's model settings
    (trees, training cipher) over them."""
    m = run.traffic["model"]
    return {**run.config["params"], "n_trees": m["trees"],
            "cipher": m["cipher"]}


def setup(run) -> State:
    from repro.core import SBTParams, VerticalBoosting
    data, t = run.config["data"], run.traffic
    fg, fh = data["guest_features"], data["host_features"]
    Xg, Xh, y = tabular.vertical(t["model"]["rows"], fg, fh,
                                 t["model"]["seed"])
    model = VerticalBoosting(SBTParams(**model_params(run)))
    scores = [model.begin_fit(Xg, y, [Xh])]
    for k in range(t["model"]["trees"]):
        scores.append(model.boost_round(k, scores[-1]))
    model.finish_fit(scores[-1])
    pool = [tabular.features(t["batch_rows"], fg, fh, run.seed, 100 + j)
            for j in range(t["pool_batches"])]
    for j in range(t["warmup_batches"]):
        bg, bh = pool[j % len(pool)]
        np.asarray(model.predict_proba(bg, [bh]))
    return State(train=(Xg, Xh, y), scores=scores[:-1], pool=pool,
                 model=model, sample=[], rng=tabular.rng_for(run.seed, 11))


def step(run, state: State, i: int) -> dict:
    j = i % len(state.pool)
    bg, bh = state.pool[j]
    t0 = time.perf_counter()
    proba = np.asarray(state.model.predict_proba(bg, [bh]))
    seconds = time.perf_counter() - t0
    k = run.traffic["check_batches"]
    if len(state.sample) < k:
        state.sample.append((i, j, proba))
    else:
        r = int(state.rng.integers(0, i + 1))
        if r < k:
            state.sample[r] = (i, j, proba)
    return {"seconds": seconds, "rows": len(proba)}


def reference_params(run) -> dict:
    p = model_params(run)
    keys = ("max_depth", "n_bins", "learning_rate", "lam", "min_leaf",
            "min_gain")
    return {k: p[k] for k in keys}


def check(run, state: State, candidates) -> dict:
    """``{candidate: {"proba_gap": value}}``.  Candidate "program" is the
    served probabilities; "float32" puts the reference, grown and scored in
    float32, in the program's place."""
    state.model = None                 # free the program's device state
    gc.collect()
    p = reference_params(run)
    Xg, Xh, y = state.train
    thr, bins_t = reference.bin_parties([Xg, Xh], p["n_bins"])
    base = reference.init_score(y)
    dtypes = [np.float64] + [np.float32] * (candidates != ("program",))
    trees = {dt: [reference.grow_tree(
        bins_t, *reference.log_loss_grad(y, s, dt), p, dt)[0]
        for s in state.scores] for dt in dtypes}
    fg = Xg.shape[1]
    gaps = {c: 0.0 for c in candidates}
    ambiguous, rows = 0, 0
    for _, j, served in state.sample:
        bg, bh = state.pool[j]
        bt = np.concatenate([reference.bin_features(bg, thr[:fg]),
                             reference.bin_features(bh, thr[fg:])])
        want, amb = reference.predict(base, trees[np.float64], bt)
        keep = ~amb
        ambiguous += int(amb.sum())
        rows += len(want)
        for c in candidates:
            got = served if c == "program" else reference.predict(
                base, trees[np.float32], bt, np.float32)[0]
            diff = np.abs(got - want)[keep]
            gap = float(diff.max()) if diff.size else 0.0
            gaps[c] = max(gaps[c], gap if gap == gap else np.inf)
    print(f"serve check: {rows} rows in {len(state.sample)} batches, "
          f"{ambiguous} left out as tie-ambiguous", file=sys.stderr,
          flush=True)
    return {c: {"proba_gap": gaps[c]} for c in candidates}
