"""Without a chip, and in a checkout holding only the benchmark, a run
exits non-zero and prints no result line."""

import json
import os
import shutil
import subprocess
import sys

from conftest import CHIP, ROOT


def _run(cwd, run_py):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(run_py), "--workload", "higgs.serve",
         "--seed", str(2 ** 31 + 9), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc) -> bool:
    lines = proc.stdout.strip().splitlines()
    if not lines:
        return True
    try:
        return "correct" not in json.loads(lines[-1])
    except ValueError:
        return True


def test_cpu_only_exits_nonzero_without_result():
    proc = _run(ROOT, CHIP / "run.py")
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "accelerator" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, tmp_path / "benchmarks" / "chip" / "run.py")
    assert proc.returncode != 0
    assert _no_result(proc)
