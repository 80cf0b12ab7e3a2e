"""A configuration, a traffic mix, a limit file and a metric added as new
files and new entries are found by name: no existing file changes."""

import json

from conftest import TINY_DATA, TINY_PARAMS, run_tiny


def test_new_files_are_found_by_name(tiny_bench):
    here = tiny_bench.here
    cfg = json.loads((here / "configs" / "higgs.json").read_text())
    cfg.update(name="wide", data=dict(TINY_DATA, host_features=9),
               params=dict(cfg["params"], **TINY_PARAMS))
    (here / "configs" / "wide.json").write_text(json.dumps(cfg))
    traffic = json.loads((here / "traffic" / "serve_closed.json")
                         .read_text())
    traffic.update(batch_rows=256, check_batches=1)
    (here / "traffic" / "serve_small.json").write_text(json.dumps(traffic))
    (here / "limits" / "wide.serve_small.json").write_text(
        json.dumps({"proba_gap": 1e-12}))
    (here / "metrics" / "batches_run.py").write_text(
        "def read(run):\n    return len(run.units)\n")
    spec = tiny_bench.spec
    spec["configs"].append({"name": "wide", "source": "test",
                            "file": "benchmarks/chip/configs/wide.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "wide.serve_small", "config": "wide",
                              "traffic": "serve_small", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "batches_run", "unit": "batches",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["wide.serve_small"]})
    (tiny_bench.root / "BENCHMARK.json").write_text(json.dumps(spec))

    import harness
    bench = harness.Bench(root=tiny_bench.root, here=here)
    out = run_tiny(bench, "wide.serve_small")
    assert out["correct"], out["checks"]
    assert out["metrics"]["batches_run"]["value"] == out["attempted"]
    # setup_s has no workloads key, so every cell reports it
    assert set(out["metrics"]) == {"batches_run", "setup_s"}


def test_every_metric_has_a_reader_that_reads_nothing_from_nothing(
        tiny_bench):
    import harness
    run = harness.Run(cell={}, config={}, traffic={}, seed=0, seconds=0.0)
    for section in ("end_to_end", "per_layer"):
        for entry in tiny_bench.spec[section]:
            reader = tiny_bench.reader(entry["name"])
            if entry["name"] != "setup_s":
                assert reader.read(run) is None, entry["name"]


def test_device_ms_per_batch_is_busy_time_over_batches(tiny_bench):
    import harness
    run = harness.Run(cell={}, config={}, traffic={}, seed=0, seconds=0.0,
                      units=[{"seconds": 0.2, "rows": 8}] * 4,
                      trace_summary={"busy_s": 0.6})
    reader = tiny_bench.reader("device_ms_per_batch.serve")
    assert abs(reader.read(run) - 150.0) < 1e-9
