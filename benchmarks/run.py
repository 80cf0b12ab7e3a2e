"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` runs reduced dataset
lists (CI); default runs the full set (minutes on CPU).  ``--json out.json``
additionally writes machine-readable results (name, us_per_call, the parsed
derived counters, and environment info) so per-PR perf trajectories can be
recorded and CI can upload the file as an artifact.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig7,...]
                                            [--json out.json]
                                            [--trace trace.json]

``--trace`` installs a process-default tracer (``repro.obs``): every
bench's guest-side spans and wire instants are recorded, each bench's
results gain a ``trace`` summary (event count, top-3 spans by self
time), and one merged Perfetto ``trace.json`` (one pid per bench) is
written at the given path — open it at https://ui.perfetto.dev.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import traceback

sys.path.insert(0, "src")

_KEY_RE = re.compile(r"^[A-Za-z_][\w./-]*$")

BENCHES = {
    "fig7_tree_build": "benchmarks.bench_tree_build",
    "table3_lossless": "benchmarks.bench_lossless",
    "fig8_modes": "benchmarks.bench_modes",
    "fig9_mo": "benchmarks.bench_mo",
    "cost_model": "benchmarks.bench_cost_model",
    "kernels": "benchmarks.bench_kernels",
    "serving": "benchmarks.bench_serving",
    "transport": "benchmarks.bench_transport",
}


def _parse_derived(derived: str) -> dict:
    """'a=1;b=2.5x;c=foo' -> {'a': 1.0, 'b': '2.5x', 'c': 'foo'} (floats
    where they parse, raw strings otherwise).  Fragments without an
    identifier-like key (e.g. 'SKIP:...' markers) land under 'notes'."""
    out = {}
    for part in str(derived).split(";"):
        k, _, v = part.partition("=")
        if _KEY_RE.match(k) and _ == "=":
            try:
                out[k] = float(v)
            except ValueError:
                out[k] = v
        elif part:
            out.setdefault("notes", []).append(part)
    return out


def _env_info() -> dict:
    import os
    info = {"python": sys.version.split()[0],
            "xla_flags": os.environ.get("XLA_FLAGS", "")}
    try:
        import jax
        info["jax"] = jax.__version__
        info["backend"] = jax.default_backend()
        info["n_devices"] = len(jax.devices())
    except Exception:                # noqa: BLE001
        pass
    return info


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None,
                    help="write machine-readable results to this path")
    ap.add_argument("--trace", default=None,
                    help="record spans and write a merged Perfetto "
                         "trace.json to this path")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.launch.cache import use_compile_cache
    use_compile_cache()

    tracer = None
    if args.trace:
        from repro.obs.trace import Tracer, set_default
        tracer = Tracer("bench", capacity=1 << 18)
        set_default(tracer)     # training code inherits it via begin_fit

    print("name,us_per_call,derived")
    failures = 0
    results = []
    trace_parties = []
    for pid, (key, mod_name) in enumerate(BENCHES.items()):
        if only and key not in only:
            continue
        print(f"# --- {key} ---", flush=True)
        if tracer is not None:
            tracer.clear()      # one clean buffer per bench
        try:
            mod = __import__(mod_name, fromlist=["main"])
            rows = mod.main(quick=args.quick) or []
            bench_results = [{"bench": key, "name": name,
                              "us_per_call": float(us),
                              "stats": _parse_derived(derived)}
                             for name, us, derived in rows]
        except Exception as e:        # noqa: BLE001
            failures += 1
            print(f"{key},0.0,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc()
            bench_results = [{"bench": key, "name": key, "us_per_call": 0.0,
                              "stats": {"error": f"{type(e).__name__}: {e}"}}]
        if tracer is not None and len(tracer):
            from repro.obs.export import merge_traces, trace_summary
            party = {"party": key, "pid": pid,
                     "events": tracer.export_events(), "offset_ns": 0}
            summ = trace_summary(merge_traces([party]),
                                 dropped=tracer.dropped)
            for r in bench_results:
                r["trace"] = summ
            trace_parties.append(party)
        results += bench_results
    if args.trace and trace_parties:
        from repro.obs.export import merge_traces, write_perfetto
        write_perfetto(args.trace, merge_traces(trace_parties),
                       trace_parties)
        print(f"# wrote trace to {args.trace}", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema_version": 2, "env": _env_info(),
                       "quick": args.quick, "results": results}, f,
                      indent=1)
        print(f"# wrote {len(results)} results to {args.json}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
