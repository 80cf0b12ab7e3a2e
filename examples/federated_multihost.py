"""One party per OS process: train and serve over a real socket transport.

The guest runs here; each host party is spawned as its own process holding
ONLY its own feature columns.  Every cross-party byte crosses a
length-prefixed localhost TCP frame: the per-layer ``assign_sync`` ->
``split_infos`` -> batched-decrypt rounds during training, and the
one-``predict_bits``-round-trip-per-host serving protocol afterwards —
served from per-party exports each process reloads from disk.

The run is checked bit-identical to the in-process Channel simulation,
with the identical per-tag wire-byte ledger; the report contrasts the
analytic ledger with the bytes the socket actually moved.

    PYTHONPATH=src python examples/federated_multihost.py [--loopback]
"""

import argparse
import sys
import tempfile

sys.path.insert(0, "src")

import numpy as np

from repro.core import SBTParams, VerticalBoosting
from repro.runtime.transport import MultiHostRun
from repro.launch.cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--loopback", action="store_true",
                    help="in-memory transport (same framing, no processes)")
    ap.add_argument("--rows", type=int, default=2000)
    args = ap.parse_args()
    transport = "loopback" if args.loopback else "socket"

    rng = np.random.default_rng(0)
    X = rng.normal(0, 1, (args.rows, 10)).astype(np.float32)
    y = (X @ np.ones(10) + 0.3 * rng.normal(0, 1, args.rows) > 0).astype(
        np.float64)
    Xg, Xh = X[:, :4], X[:, 4:]
    params = SBTParams(n_trees=4, max_depth=3, n_bins=16, cipher="affine",
                       key_bits=256, precision=20, seed=1)

    print("in-process oracle...")
    ref = VerticalBoosting(params).fit(Xg, y, [Xh])

    print(f"multi-host run ({transport}): guest + 1 host process...")
    with MultiHostRun(params, [Xh], transport=transport,
                      export_dir=tempfile.mkdtemp()) as run:
        model = run.fit(Xg, y)
        print("  train bit-identical:",
              bool(np.array_equal(model.train_score_, ref.train_score_)))
        print("  per-tag ledger identical:",
              run.channel.summary() == ref.channel.summary())
        print(f"  control round-trip: {run.ping() * 1e3:.2f} ms")

        run.serve()                      # per-party exports, reloaded
        score = run.predict_score(Xg, staged=True)   # training rows
        print("  serve bit-identical:",
              bool(np.array_equal(score, ref.predict_score(Xg, [Xh]))))

        ledger = run.channel.total_bytes
        sock = run.channel.total_tx_bytes + run.channel.total_rx_bytes
        print(f"  ledger (protocol-fidelity): {ledger} B; "
              f"socket (framed): {sock} B ({sock / ledger:.2f}x)")
        host = run.host_stats()[0]
        print(f"  host-side HE work (its own process): "
              f"hom_add={host['stats']['n_hom_add']}, "
              f"hist_launches={host['stats']['n_hist_launches']}")


if __name__ == "__main__":
    main()
