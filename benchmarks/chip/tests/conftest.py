"""Self-tests of the chip benchmark, run on the CPU:

    python -m pytest benchmarks/chip/tests

They sit outside the repository's ``testpaths``.  ``tiny_bench`` copies
the benchmark into a temporary checkout with every cell cut to a size the
CPU runs in seconds (Pallas kernels in interpret mode)."""

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(ROOT / "src"))

TINY_DATA = {"rows": 1500, "guest_features": 3, "host_features": 4}
TINY_PARAMS = {"key_bits": 256, "max_depth": 3, "n_bins": 16}
TINY_SERVE = {"batch_rows": 512, "pool_batches": 3, "warmup_batches": 1,
              "check_batches": 2,
              "model": {"rows": 600, "trees": 3, "cipher": "plain",
                        "seed": 0}}


def edit_json(path: Path, fn) -> None:
    data = json.loads(path.read_text())
    fn(data)
    path.write_text(json.dumps(data))


@pytest.fixture
def tiny_bench(tmp_path):
    """A checkout holding BENCHMARK.json and the benchmark's files, every
    configuration and traffic mix cut to a tiny size."""
    import harness
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP, here, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata", "tests"))
    for cfg in (here / "configs").glob("*.json"):
        edit_json(cfg, lambda c: (c["data"].update(TINY_DATA),
                                  c["params"].update(TINY_PARAMS)))
    edit_json(here / "traffic" / "serve_closed.json",
              lambda t: t.update(TINY_SERVE))
    return harness.Bench(root=tmp_path, here=here)


def run_tiny(bench, cell: str, seed: int = 2 ** 33 + 17,
             seconds: float = 0.5, candidates=("program",)) -> dict:
    import time

    import harness
    return harness.run_cell(bench, cell, seed, seconds, False,
                            time.perf_counter(), chip_check=False,
                            candidates=candidates, log=lambda m: None)
