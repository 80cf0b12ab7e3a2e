"""Runs one benchmark cell once and builds its result line.

Everything that belongs to one cell is found by name, so a later cell,
configuration, traffic mix or metric is added as files and entries only:

* the cell (``workloads`` entry of ``BENCHMARK.json``) names a
  configuration and a traffic mix;
* the configuration's file (``configs`` entry) holds its sizes and
  ``SBTParams``;
* ``traffic/<traffic>.json`` holds the mix's parameters, and its ``kind``
  names the module ``kinds/<kind>.py`` that sets the cell up, runs one unit
  of work (a boosting round, a served batch) and checks the results;
* ``limits/<cell>.json`` holds the limit of each number the check compares;
* ``metrics/<metric>.py`` reads one metric from the run (``read(run)``,
  ``None`` where it finds nothing to read).

A kind module provides ``SPAN`` (the name of its unit's trace span),
``setup(run) -> state``, ``step(run, state, i) -> dict`` (one unit's record,
with its ``seconds``) and ``check(run, state, candidates) -> {candidate:
{name: value}}``, which frees the program's state before it runs the
reference.  Candidate "program" is what the timed path produced; another
candidate (the control, "float32") stands in the program's place.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import compile_cache
import trace_reduce

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path):
    path = Path(path)
    name = "chipbench_" + path.parent.name + "_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files it names, looked up by name."""

    def __init__(self, root: Path = ROOT, here: Path = HERE):
        self.root, self.here = Path(root), Path(here)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _entry(self, section: str, name: str) -> dict:
        for entry in self.spec[section]:
            if entry["name"] == name:
                return entry
        raise KeyError(f"no {section} entry named {name!r}")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        entry = self._entry("configs", name)
        return json.loads((self.root / entry["file"]).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.here / "traffic" / f"{name}.json")
                          .read_text())

    def limits(self, cell: str) -> dict:
        return json.loads((self.here / "limits" / f"{cell}.json")
                          .read_text())

    def kind(self, kind: str):
        return load_module(self.here / "kinds" / f"{kind}.py")

    def reader(self, metric: str):
        return load_module(self.here / "metrics" / f"{metric}.py")

    def metrics(self, cell: str, section: str) -> list:
        """The metrics of ``section`` that ``cell`` reports."""
        return [m for m in self.spec[section]
                if cell in m.get("workloads", [cell])]


@dataclasses.dataclass
class Run:
    """What a run measured; metric readers read it."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    device_kind: str = ""
    setup_s: float = math.nan
    window_s: float = math.nan
    units: list = dataclasses.field(default_factory=list)
    trace_summary: dict | None = None
    window_compiles: int = 0
    memory_peak_bytes: int = 0


class CompileCounter:
    """Counts executables built while it is armed: compiled, or read from
    the persistent cache (a build event either way; a read adds a cache
    event)."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    READ = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax
        self.armed = False
        self.built = self.read = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if not self.armed:
            return
        if event == self.BUILD:
            self.built += 1
            self.seconds += secs
        elif event == self.READ:
            self.read += 1

    def take(self) -> tuple:
        """(built, read from the cache, seconds building) since the last
        take, and start counting afresh."""
        out = (self.built, self.read, self.seconds)
        self.built = self.read = 0
        self.seconds = 0.0
        return out


def require_chip(chips: int) -> tuple:
    import jax
    backend = jax.default_backend()
    devices = jax.devices()
    if backend == "cpu" or len(devices) < chips:
        raise NoChip(f"JAX backend {backend!r} with {len(devices)} "
                     f"device(s); the cell needs {chips} accelerator(s)")
    return devices


def _memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


def _window(run: Run, kind, state, span_starts: list) -> None:
    import jax
    t0 = time.perf_counter()
    i = 0
    with jax.profiler.TraceAnnotation("window"):
        while time.perf_counter() - t0 < run.seconds:
            span_starts.append(time.perf_counter_ns())
            with jax.profiler.TraceAnnotation(kind.SPAN):
                run.units.append(kind.step(run, state, i))
            i += 1
    run.window_s = time.perf_counter() - t0


def run_cell(bench: Bench, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, chip_check: bool = True,
             candidates=("program",), log=print) -> dict:
    """Set the cell up, run its window, check it, and return the result
    line's object.  ``correct`` judges the first of ``candidates``; with
    more than one, ``readings`` holds every candidate's numbers
    (``control.py``)."""
    cell = bench.cell(cell_name)
    traffic = bench.traffic(cell["traffic"])
    kind = bench.kind(traffic["kind"])
    limits = bench.limits(cell_name)
    cache_dir = compile_cache.place()
    import jax
    devices = require_chip(cell["chips"]) if chip_check else jax.devices()
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    run = Run(cell=cell, config=bench.config(cell["config"]),
              traffic=traffic, seed=seed, seconds=seconds,
              device_kind=dev.device_kind)
    counter = CompileCounter()
    program_tracer = None
    if trace:
        from repro.obs import trace as obs_trace
        program_tracer = obs_trace.Tracer("bench")
        obs_trace.set_default(program_tracer)

    counter.armed = True
    state = kind.setup(run)
    built, read, secs = counter.take()
    log(f"set-up built {built} executables, {read} of them read from the "
        f"persistent cache, in {secs:.3f} s")
    trace_dir = None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        trace_dir = Path(tempfile.mkdtemp(prefix="chipbench_trace_"))
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    span_starts: list = []
    run.setup_s = time.perf_counter() - t_start
    log(f"window opens after {run.setup_s:.3f} s of set-up")
    try:
        _window(run, kind, state, span_starts)
    finally:
        counter.armed = False
        if trace:
            jax.profiler.stop_trace()
    run.window_compiles = counter.take()[0]
    run.memory_peak_bytes = _memory_peak()
    log(f"setup_s {run.setup_s:.3f}; window {run.window_s:.3f} s, "
        f"{len(run.units)} units; executables built in the window: "
        f"{run.window_compiles}; memory peak {run.memory_peak_bytes} B")

    if trace:
        try:
            run.trace_summary = trace_reduce.reduce(
                trace_reduce.find_xplane(trace_dir), window="window",
                step=kind.SPAN, step_starts_ns=span_starts,
                program_events=program_tracer.export_events())
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    with jax.profiler.TraceAnnotation("reference"):
        readings = kind.check(run, state, tuple(candidates))
    values = readings[candidates[0]]
    checks = {name: {"value": float(values[name]),
                     "limit": float(limits[name])} for name in limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for entry in bench.metrics(cell_name, section):
        value = bench.reader(entry["name"]).read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": bool(correct), "attempted": len(run.units),
              "failed": 0, "metrics": metrics, "device": device}
    if run.trace_summary is not None:
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
        result["breakdown"] = {
            "device_ops": run.trace_summary["device_ops"],
            "idle_gaps": run.trace_summary["idle_gaps"]}
    if len(candidates) > 1:
        result["readings"] = readings
    result["checks"] = checks
    return result


def print_result(result: dict) -> None:
    """The compared numbers as the last lines of standard error, and the
    result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
