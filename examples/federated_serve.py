"""Train -> export per party -> serve from reloaded halves.

The serving lifecycle end to end: a vertical federated model is trained
with the affine cipher, each party's half is exported to its own directory
(guest: structure + leaf weights + its splits; host: its splits + binning
only), the halves are reloaded with no training objects in sight, and a
batch is served through the round-batched bit protocol — ONE wire
round-trip per host per batch — then checked bit-identical against the
legacy per-node loop.

    PYTHONPATH=src python examples/federated_serve.py [--out DIR]
"""

import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, "src")

import numpy as np

from repro.core import SBTParams, VerticalBoosting
from repro.serving import FederatedPredictor, export_model, load_ensemble
from repro.launch.cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="export directory (default: a temp dir)")
    ap.add_argument("--rows", type=int, default=20000,
                    help="serving batch size")
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    X = rng.normal(0, 1, (3000, 10)).astype(np.float32)
    y = (X @ np.ones(10) + 0.3 * rng.normal(0, 1, 3000) > 0).astype(
        np.float64)
    Xg, Xh = X[:, :4], X[:, 4:]

    print("training (affine cipher, 2 parties)...")
    model = VerticalBoosting(SBTParams(n_trees=6, max_depth=4, n_bins=16,
                                       cipher="affine", key_bits=256,
                                       precision=20, seed=1))
    model.fit(Xg, y, [Xh])

    out = args.out or os.path.join(tempfile.mkdtemp(), "model")
    export_model(model, out)
    print(f"exported per-party halves to {out}: {sorted(os.listdir(out))}")

    # a serving process would load ONLY its own half; the simulation loads
    # all of them and wires them through one predictor + byte ledger
    ens = load_ensemble(out)
    pred = FederatedPredictor(ens.guest, ens.hosts)

    n = args.rows
    Xs = rng.normal(0, 1, (n, 10)).astype(np.float32)
    pred.predict_score(Xs[:, :4], [Xs[:, 4:]])      # compile
    t0 = time.time()
    score = pred.predict_score(Xs[:, :4], [Xs[:, 4:]])
    dt = time.time() - t0

    legacy = model.predict_score(Xs[:, :4], [Xs[:, 4:]], packed=False)
    ch = pred.channel.summary()
    batches = pred.stats.n_predict_batches
    wire = sum(v["bytes"] for v in ch.values()) / batches / n
    print(f"served {n} rows in {dt * 1e3:.1f} ms "
          f"({n / dt:.0f} rows/s from reloaded halves)")
    print(f"bit-identical to the legacy loop: "
          f"{bool(np.array_equal(score, legacy))}")
    print(f"wire: {wire:.1f} bytes/instance, "
          f"{pred.stats.n_predict_roundtrips // batches} round-trip(s) "
          f"per host per batch")
    print("ledger:", {k: v["bytes"] for k, v in ch.items()})


if __name__ == "__main__":
    main()
