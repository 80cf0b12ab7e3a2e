#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python benchmarks/chip/run.py --workload higgs.train --seed 7 \\
        --seconds 30 --trace 0

Sets the cell up (data and model from ``--seed``, warm-up, compilation),
measures for ``--seconds``, checks what the timed path produced against
the plain reference, and prints the result as the last line of standard
output: one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window), ``device``,
``breakdown`` (``--trace 1``) and, last, ``checks``: each compared number
beside its limit.  Exits non-zero with no result line where JAX finds no
accelerator, or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    import harness

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    try:
        result = harness.run_cell(harness.Bench(), args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START,
                                  log=log)
    except harness.NoChip as e:
        log(f"run.py: {e}")
        return 3
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
