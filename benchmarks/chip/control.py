#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's
numbers on many seeds, in one process.

    python benchmarks/chip/control.py --workload higgs.train \\
        --seeds 11,12,13 --seconds 15

For each seed the cell is set up and run as ``run.py`` runs it (for
``--seconds``), and its check compares both the program's output and the
control's (the reference computed in float32, one precision below the
float64 the configuration states) with the float64 reference, on the same
sampled rounds or batches.  One line per seed, then the largest program
reading (the lower reading) and the smallest control reading (the upper
one).  The benchmark's own runs never run the control.
"""

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated non-negative whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]

    import harness

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    bench = harness.Bench()
    lower, upper = {}, {}
    for seed in seeds:
        t0 = time.perf_counter()
        try:
            out = harness.run_cell(bench, args.workload, seed, args.seconds,
                                   False, t0, log=log,
                                   candidates=("program", "float32"))
        except harness.NoChip as e:
            log(f"control.py: {e}")
            return 3
        r = out["readings"]
        for name, v in r["program"].items():
            lower[name] = max(lower.get(name, 0.0), v)
        for name, v in r["float32"].items():
            upper[name] = min(upper.get(name, float("inf")), v)
        print(json.dumps({"seed": seed, "program": r["program"],
                          "float32": r["float32"],
                          "attempted": out["attempted"],
                          "setup_s": out["metrics"].get("setup_s")}),
              flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(seeds),
                      "lower": lower, "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
