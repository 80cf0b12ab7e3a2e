"""Observability layer (DESIGN.md §14): tracer/metrics units, the Stats
merge round-trip property, monotonic liveness, and the federated trace
audit — tracing DISABLED must leave the transport runs bit-identical
with ≤2% wall-time overhead; tracing ENABLED must produce a merged
Perfetto trace whose per-party wire-event byte sums equal the converged
per-tag ``Channel`` ledger totals exactly (the trace is audited, not
decorative).
"""

import dataclasses
import json
import os
import tempfile
import time

import numpy as np
import pytest

try:        # property tests run where hypothesis exists (the CI jobs
            # install it); the deterministic cases below run everywhere
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import SBTParams, VerticalBoosting
from repro.core.party import Stats
from repro.obs.export import (audit_wire_events, estimate_offset,
                              merge_traces, self_time, trace_summary,
                              waterfall, wire_bytes_by_tag, write_perfetto)
from repro.obs.trace import NULL_TRACER, Tracer, _NULL_SPAN
from repro.runtime.transport import MultiHostRun


def _data(n=300, d=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(0, 1, (n, d)).astype(np.float32)
    w = rng.normal(0, 1, d)
    y = (X @ w + 0.3 * rng.normal(0, 1, n) > 0).astype(np.float64)
    return X, y


# ---------------------------------------------------------------------------
# tracer + metrics units
# ---------------------------------------------------------------------------

def test_tracer_span_instant_complete_and_ring_drop():
    tr = Tracer("t", capacity=4)
    with tr.span("a", tree=1):
        pass
    tr.instant("b", cat="wire", nbytes=7)
    tr.complete("c", 100, 50, depth=2)
    assert len(tr) == 3 and tr.dropped == 0
    for _ in range(10):
        tr.instant("spam")
    assert len(tr) == 4                  # bounded ring: oldest dropped
    assert tr.dropped == 9
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_tracer_span_records_exception_and_duration():
    tr = Tracer("t")
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    ph, name, cat, ts, dur, tid, attrs = tr.export_events()[0]
    assert (ph, name, attrs["error"]) == ("X", "boom", "ValueError")
    assert dur >= 0 and ts > 0


def test_null_tracer_is_free_and_shared():
    assert NULL_TRACER.span("x") is _NULL_SPAN
    NULL_TRACER.instant("x")
    NULL_TRACER.complete("x", 0, 1)
    assert len(NULL_TRACER) == 0


def test_negative_duration_clamped():
    tr = Tracer("t")
    tr.complete("backwards", 100, -5)
    assert tr.export_events()[0][4] == 0


def test_estimate_offset_min_rtt_sample_wins():
    # sample 2 has the smaller RTT (4 ns) -> its midpoint decides
    samples = [(0, 1000, 100), (10, 1007, 14)]
    off, rtt = estimate_offset(samples)
    assert (off, rtt) == (1007 - 12, 4)
    assert estimate_offset([]) == (0, 0)


def test_merge_and_self_time_nested_attribution():
    ev = [["X", "outer", "train", 0, 100, 1, {}],
          ["X", "inner", "train", 10, 30, 1, {}]]
    merged = merge_traces([{"party": "p", "pid": 0, "events": ev,
                            "offset_ns": 0}])
    st_ = self_time(merged)
    assert st_ == {"outer": 70, "inner": 30}
    summ = trace_summary(merged)
    assert summ["events"] == 2
    assert summ["top_self_time"][0]["name"] == "outer"


def test_merge_applies_clock_offset():
    ev = [["i", "e", "wire", 1000, 0, 1, {"tag": "t", "nbytes": 3}]]
    merged = merge_traces([{"party": "h", "pid": 1, "events": ev,
                            "offset_ns": 400}])
    assert merged[0]["ts_ns"] == 600


def test_wire_audit_detects_mismatch_and_passes_exact():
    ev = [["i", "enc_gh", "wire", 0, 0, 1, {"tag": "enc_gh", "nbytes": 10}],
          ["i", "enc_gh", "wire", 1, 0, 1, {"tag": "enc_gh", "nbytes": 5}],
          ["X", "ship", "transport", 2, 9, 1,
           {"tag": "enc_gh", "nbytes": 999}]]      # physical: excluded
    assert wire_bytes_by_tag(ev) == {"enc_gh": 15}
    assert audit_wire_events(ev, {"enc_gh": 15}) == {}
    assert audit_wire_events(ev, {"enc_gh": 16}) == {"enc_gh": (15, 16)}
    assert audit_wire_events(ev, {"enc_gh": 15, "other": 4}) == {
        "other": (0, 4)}


def test_perfetto_export_and_waterfall(tmp_path):
    ev = [["X", "layer", "train", 1000, 2000, 1, {"tree": 0}],
          ["i", "mark", "chaos", 1500, 0, 1, {}]]
    merged = merge_traces([{"party": "guest", "pid": 0, "events": ev,
                            "offset_ns": 0}])
    path = tmp_path / "trace.json"
    write_perfetto(str(path), merged,
                   [{"party": "guest", "pid": 0}])
    data = json.loads(path.read_text())
    phases = [e["ph"] for e in data["traceEvents"]]
    assert phases == ["M", "X", "i"]
    assert data["traceEvents"][1]["dur"] == 2.0     # µs
    text = waterfall(merged)
    assert "tree 0" in text and "layer" in text


def test_metrics_registry_snapshot_and_clear():
    from repro.obs.metrics import MetricsRegistry
    m = MetricsRegistry()
    m.counter("c").add(2)
    m.counter("c").add()
    m.gauge("g").observe(5)
    m.gauge("g").observe(3)              # gauge keeps the max
    m.histogram("h").observe(1.0)
    m.histogram("h").observe(3.0)
    m.series("s").data.extend([1, 2])
    snap = m.snapshot()
    assert snap["counters"]["c"] == 3.0
    assert snap["gauges"]["g"] == 5.0
    assert snap["histograms"]["h"]["count"] == 2
    assert snap["histograms"]["h"]["mean"] == 2.0
    assert snap["series"]["s"] == [1, 2]
    m.clear()
    assert m.snapshot() == {"counters": {}, "gauges": {}, "histograms": {},
                            "series": {}}


# ---------------------------------------------------------------------------
# Stats: metrics-backed timers + version-skew-safe merge
# ---------------------------------------------------------------------------

def test_stats_timer_and_series_properties_behave_like_fields():
    s = Stats()
    s.encrypt_seconds += 1.5
    s.encrypt_seconds += 0.5
    assert s.encrypt_seconds == 2.0
    s.tree_seconds.append(0.25)
    s.tree_seconds.extend([0.5, 0.75])
    del s.tree_seconds[1:]               # rollback_to_round truncation
    assert s.tree_seconds == [0.25]
    d = s.as_dict()                      # wire format keeps the old keys
    assert d["encrypt_seconds"] == 2.0 and d["tree_seconds"] == [0.25]
    s2 = Stats()
    s2.merge_counts(d)
    assert s2.encrypt_seconds == 2.0 and s2.tree_seconds == [0.25]


def _merge_roundtrip_case(parties):
    """Merging N per-party ``as_dict()`` snapshots must reconstruct the
    single shared-Stats view of an in-process run: counters add, gauges
    max, lists concatenate (integer-valued floats keep sums exact)."""
    shared = Stats()
    dicts = []
    for p in parties:
        s = Stats()
        for k in ("n_encrypt", "n_hom_add"):
            setattr(s, k, getattr(s, k) + p[k])
            setattr(shared, k, getattr(shared, k) + p[k])
        s.peak_frontier = max(s.peak_frontier, p["peak_frontier"])
        shared.peak_frontier = max(shared.peak_frontier, p["peak_frontier"])
        for k in ("encrypt_seconds", "host_wait_seconds"):
            setattr(s, k, getattr(s, k) + float(p[k]))
            setattr(shared, k, getattr(shared, k) + float(p[k]))
        for k in ("tree_seconds", "layer_overlap"):
            getattr(s, k).extend(float(v) for v in p[k])
            getattr(shared, k).extend(float(v) for v in p[k])
        dicts.append(s.as_dict())
    merged = Stats()
    for d in dicts:
        merged.merge_counts(d)
    assert merged.as_dict() == shared.as_dict()
    assert merged.unmerged == {}


def test_stats_merge_roundtrip_deterministic_cases():
    _merge_roundtrip_case([
        {"n_encrypt": 3, "n_hom_add": 0, "peak_frontier": 7,
         "encrypt_seconds": 2, "host_wait_seconds": 0,
         "tree_seconds": [1, 2], "layer_overlap": []},
        {"n_encrypt": 0, "n_hom_add": 11, "peak_frontier": 2,
         "encrypt_seconds": 5, "host_wait_seconds": 3,
         "tree_seconds": [], "layer_overlap": [4]},
        {"n_encrypt": 1, "n_hom_add": 1, "peak_frontier": 1,
         "encrypt_seconds": 0, "host_wait_seconds": 0,
         "tree_seconds": [0], "layer_overlap": [0, 0]},
    ])


if HAVE_HYPOTHESIS:
    _INT = st.integers(0, 1000)

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.fixed_dictionaries({
        "n_encrypt": _INT, "n_hom_add": _INT, "peak_frontier": _INT,
        "encrypt_seconds": _INT, "host_wait_seconds": _INT,
        "tree_seconds": st.lists(_INT, max_size=4),
        "layer_overlap": st.lists(_INT, max_size=3),
    }), min_size=1, max_size=4))
    def test_stats_merge_roundtrip_matches_shared(parties):
        _merge_roundtrip_case(parties)


def test_stats_merge_version_skew_lands_in_unmerged():
    s = Stats()
    s.merge_counts({"future_counter": 3, "future_list": [1], "n_encrypt": 2})
    s.merge_counts({"future_counter": 4, "future_list": [2],
                    "future_tag": "x"})
    assert s.n_encrypt == 2
    assert s.unmerged == {"future_counter": 7, "future_list": [1, 2],
                          "future_tag": "x"}


# ---------------------------------------------------------------------------
# monotonic liveness (runtime/fault.py satellite)
# ---------------------------------------------------------------------------

def test_heartbeat_liveness_survives_wallclock_steps(tmp_path):
    """An NTP wall-clock step must not change the liveness verdict: a
    beat whose file mtime CHANGED is alive even if the stamp reads hours
    in the past (backward step), and a peer is wedged only after its
    mtime stays unchanged for ``timeout`` seconds of the observer's own
    monotonic clock."""
    from repro.runtime.fault import Heartbeat
    path = str(tmp_path / "hb")
    Heartbeat(path).beat()
    assert Heartbeat.is_alive(path, timeout=5.0)
    # backward wall-clock step: the beat's stamp/mtime jumps an hour into
    # the past — under the old wall-clock compare this read as >timeout
    # stale and triggered a pointless restart
    past = time.time() - 3600
    os.utime(path, (past, past))
    assert Heartbeat.is_alive(path, timeout=5.0)
    # the mtime keeps CHANGING (peer still beating on its skewed clock):
    # alive, forever, regardless of the stamp value
    os.utime(path, (past - 100, past - 100))
    assert Heartbeat.is_alive(path, timeout=5.0)
    # mtime UNCHANGED past the monotonic timeout: wedged
    time.sleep(0.05)
    assert not Heartbeat.is_alive(path, timeout=0.01)
    # missing file: dead
    assert not Heartbeat.is_alive(str(tmp_path / "gone"), timeout=5.0)


# ---------------------------------------------------------------------------
# federated runs: disabled = bit-identical + cheap; enabled = audited
# ---------------------------------------------------------------------------

def _fit_params(**kw):
    base = dict(n_trees=2, max_depth=3, n_bins=16, cipher="plain", seed=3)
    base.update(kw)
    return SBTParams(**base)


def test_loopback_tracing_enabled_is_audited_per_party():
    """Loopback 2-party run with tracing on: each party's wire-event
    byte sums must equal its converged per-tag ledger totals EXACTLY,
    and the model must match a tracing-off oracle bit for bit (tracing
    is observation only, never control flow)."""
    X, y = _data(n=300)
    Xg, Xh = X[:, :3], [X[:, 3:]]
    ref = VerticalBoosting(_fit_params()).fit(Xg, y, Xh)
    run = MultiHostRun(_fit_params(trace=True), Xh, transport="loopback",
                       export_dir=tempfile.mkdtemp())
    try:
        model = run.fit(Xg, y)
        np.testing.assert_array_equal(model.train_score_, ref.train_score_)
        assert run.channel.summary() == ref.channel.summary()
        # guest audit: its tracer vs its own ledger
        assert model.tracer.enabled and model.tracer.dropped == 0
        assert audit_wire_events(model.tracer.export_events(),
                                 run.channel.totals) == {}
        # host audit: its own tracer vs its own (converged) ledger
        pp = run.parties[0]
        assert pp.tracer.enabled and pp.tracer.dropped == 0
        assert audit_wire_events(pp.tracer.export_events(),
                                 pp.channel.totals) == {}
        # both parties recorded training spans, not just wire instants
        g_names = {e[1] for e in model.tracer.export_events()}
        h_names = {e[1] for e in pp.tracer.export_events()}
        assert {"round", "tree", "layer", "encrypt"} <= g_names
        assert "host_layer" in h_names
    finally:
        run.close()


def test_loopback_trace_merge_and_party_status(tmp_path):
    X, y = _data(n=250, seed=1)
    Xg, Xh = X[:, :3], [X[:, 3:]]
    run = MultiHostRun(_fit_params(trace=True), Xh, transport="loopback",
                       export_dir=tempfile.mkdtemp())
    try:
        run.fit(Xg, y)
        path = tmp_path / "trace.json"
        merged = run.trace(str(path))
        assert {e["party"] for e in merged} == {"guest", "host0"}
        data = json.loads(path.read_text())
        meta = {e["args"]["name"] for e in data["traceEvents"]
                if e["ph"] == "M"}
        assert meta == {"guest", "host0"}
        assert "tree 0" in waterfall(merged)
        # live introspection over the control plane
        status = run.party_status(0)
        assert status["trace"]["enabled"] and status["trace"]["events"] > 0
        assert status["stats"]["n_hist_launches"] > 0
        assert status["n_complete"] >= 1
        # per-tag RTT histograms landed in the guest's transport metrics
        rtts = run.channel.metrics.snapshot()["histograms"]
        assert any(k.startswith("rtt:") for k in rtts)
    finally:
        run.close()


def test_socket_tracing_disabled_bit_identical_enabled_audited(tmp_path):
    """The acceptance run: forced-2-process socket training.  With
    tracing DISABLED the model and per-tag ledgers are identical to the
    in-process oracle (zero-cost contract); with tracing ENABLED the
    model is STILL bit-identical, and the merged Perfetto trace's
    guest+host wire spans sum exactly to the per-tag ledger totals."""
    X, y = _data(n=250)
    Xg, Xh = X[:, :3], [X[:, 3:]]
    ref = VerticalBoosting(_fit_params()).fit(Xg, y, Xh)

    run = MultiHostRun(_fit_params(), Xh, transport="socket",
                       export_dir=tempfile.mkdtemp(), timeout=300.0)
    try:
        model_off = run.fit(Xg, y)
        np.testing.assert_array_equal(model_off.train_score_,
                                      ref.train_score_)
        assert run.channel.summary() == ref.channel.summary()
        assert not model_off.tracer.enabled     # NULL tracer end to end
    finally:
        run.close()

    run = MultiHostRun(_fit_params(trace=True), Xh, transport="socket",
                       export_dir=tempfile.mkdtemp(), timeout=300.0)
    try:
        model = run.fit(Xg, y)
        np.testing.assert_array_equal(model.train_score_, ref.train_score_)
        assert run.channel.summary() == ref.channel.summary()
        # guest audit against the converged ledger
        assert audit_wire_events(model.tracer.export_events(),
                                 run.channel.totals) == {}
        # host audit: its trace ships over the trace_sync control tag;
        # its ledger converged to the same per-tag totals by mirroring
        dumps = run.collect_traces()
        assert dumps[0]["dropped"] == 0
        assert audit_wire_events(dumps[0]["events"],
                                 run.channel.totals) == {}
        # one merged Perfetto file with BOTH parties' events on the
        # guest timeline
        path = tmp_path / "trace.json"
        merged = run.trace(str(path))
        assert {e["party"] for e in merged} == {"guest", "host0"}
        assert path.stat().st_size > 0
        # host status over the wire mirrors the local status() shape
        status = run.party_status(0)
        assert status["trace"]["enabled"]
        assert "transport" in status and "metrics" in status
    finally:
        run.close()


def test_tracing_off_overhead_within_bound():
    """The zero-cost-when-disabled contract, measured: paired loopback
    fits with the obs layer present-but-disabled vs enabled.  The
    DISABLED side is the default path every existing benchmark takes, so
    it must not regress; the bound is the same style as PR 6's
    ``resilient_overhead`` (min-of-N, small tolerance)."""
    X, y = _data(n=400)
    Xg, Xh = X[:, :3], [X[:, 3:]]

    def one_fit(trace: bool) -> float:
        run = MultiHostRun(_fit_params(trace=trace), Xh,
                           transport="loopback",
                           export_dir=tempfile.mkdtemp())
        try:
            t0 = time.perf_counter()
            run.fit(Xg, y)
            return time.perf_counter() - t0
        finally:
            run.close()

    one_fit(False)                       # warm the jits once per side —
    one_fit(True)                        # both paths hit the same caches
    # timing in CI is noisy: interleave the sides so machine-load drift
    # hits both equally, take min-of-N per side, and accept the first
    # attempt that lands inside the bound
    last = None
    for _ in range(4):
        offs, ons = [], []
        for _ in range(4):
            offs.append(one_fit(False))
            ons.append(one_fit(True))
        last = (min(ons) / min(offs) - 1) * 100
        if last <= 2.0:
            return
    pytest.fail(f"tracing-enabled overhead {last:.2f}% > 2% "
                f"(disabled path must stay free; enabled must stay cheap)")


# ---------------------------------------------------------------------------
# chaos: injected faults must appear in the trace (CI chaos job, -k chaos)
# ---------------------------------------------------------------------------

def test_chaos_injected_fault_appears_as_trace_event():
    """Every FaultPlan rule that fires becomes an annotated ``chaos``
    instant in the faulted party's trace — collected over ``trace_sync``
    from the real spawned host process."""
    from repro.runtime.chaos import RECV, Delay, FaultPlan
    X, y = _data(n=200)
    Xg, Xh = X[:, :3], [X[:, 3:]]
    plans = {0: FaultPlan(rules=[Delay(tag="assign_sync", nth=1,
                                       direction=RECV, seconds=0.01)])}
    run = MultiHostRun(_fit_params(n_trees=1, max_depth=2, trace=True),
                       Xh, transport="socket", fault_plans=plans,
                       export_dir=tempfile.mkdtemp(), timeout=300.0)
    try:
        run.fit(Xg, y)
        events = run.collect_traces()[0]["events"]
        chaos = [e for e in events if e[2] == "chaos"]
        assert len(chaos) == 1
        ph, name, cat, ts, dur, tid, attrs = chaos[0]
        assert name == "fault_injected"
        assert attrs["rule"] == "Delay"
        assert attrs["tag"] == "assign_sync" and attrs["count"] == 1
    finally:
        run.close()


# ---------------------------------------------------------------------------
# spans on the profiler's clock, where the round's time goes
# ---------------------------------------------------------------------------

# every phase span an in-process fit records
FIT_PHASES = {"grad", "encrypt", "encode", "host_begin_tree", "layer",
              "host_layer", "guest_hist", "decrypt_wait", "decrypt",
              "decode", "split", "tree", "score_update"}


def _host_event_names(trace_dir) -> set:
    """Names of the events on the host planes of a ``jax.profiler``
    trace written under ``trace_dir``."""
    from pathlib import Path

    from jax.profiler import ProfileData
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    profile = ProfileData.from_file(str(path))
    return {e.name for plane in profile.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def _inside(child, parent) -> bool:
    return (child[5] == parent[5] and child[3] >= parent[3]
            and child[3] + child[4] <= parent[3] + parent[4])


def test_enabled_span_is_on_the_profiler_host_plane(tmp_path):
    import jax
    on, off = Tracer("on"), Tracer("off", enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with on.span("obs_probe_on"):
            time.sleep(0.001)
        with off.span("obs_probe_off"):
            time.sleep(0.001)
        with off.timed("obs_probe_timed_off") as timer:
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(tmp_path)
    assert "obs_probe_on" in names
    assert not names & {"obs_probe_off", "obs_probe_timed_off"}
    assert [e[1] for e in on.export_events()] == ["obs_probe_on"]
    # disabled: the shared no-op span, nothing recorded; a timed phase
    # still measures what its Stats timer needs
    assert off.span("x") is _NULL_SPAN and len(off) == 0
    assert timer.seconds >= 0.001


@pytest.mark.parametrize("name", ["window", "round", "serve_batch",
                                  "reference"])
def test_reserved_harness_names_are_refused_as_spans(name):
    from repro.obs.trace import RESERVED
    assert name in RESERVED
    tr = Tracer("t")
    with pytest.raises(ValueError):
        tr.span(name)
    with pytest.raises(ValueError):
        tr.timed(name)
    tr.complete(name, 0, 1)           # the ring record stays allowed
    assert len(tr) == 1


def test_inprocess_fit_records_every_phase_on_the_profiler(tmp_path):
    """A tiny affine fit records each phase of the round, nested where
    the work nests, and every span reaches the profiler's host plane
    under its own name; none under a harness's reserved name."""
    import jax
    from repro.obs.trace import RESERVED
    X, y = _data(n=300)
    params = _fit_params(cipher="affine", key_bits=256, trace=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        model = VerticalBoosting(params).fit(X[:, :3], y, [X[:, 3:]])
    finally:
        jax.profiler.stop_trace()
    ev = [e for e in model.tracer.export_events() if e[0] == "X"]
    names = {e[1] for e in ev}
    assert FIT_PHASES <= names
    assert "dispatch" not in names

    def named(n):
        return [e for e in ev if e[1] == n]
    for child, parent in (("encode", "encrypt"), ("encrypt", "tree"),
                          ("host_begin_tree", "tree"),
                          ("host_layer", "layer"), ("guest_hist", "layer"),
                          ("decrypt_wait", "layer"),
                          ("decrypt", "decrypt_wait"),
                          ("decode", "decrypt_wait"), ("layer", "tree"),
                          ("split", "tree"), ("tree", "round"),
                          ("grad", "round"), ("score_update", "round")):
        for c in named(child):
            assert any(_inside(c, p) for p in named(parent)), (child, parent)
    # one decrypt and one decode per host per layer, one split per layer
    assert len(named("decrypt")) == len(named("decode")) \
        == len(named("layer")) == len(named("split"))
    host = _host_event_names(tmp_path)
    assert FIT_PHASES <= host
    assert not host & RESERVED


def test_serve_batch_records_leaf_sum():
    X, y = _data(n=300)
    model = VerticalBoosting(_fit_params(trace=True)).fit(
        X[:, :3], y, [X[:, 3:]])
    model.tracer.clear()
    model.predict_proba(X[:, :3], [X[:, 3:]])
    ev = [e for e in model.tracer.export_events() if e[0] == "X"]
    names = [e[1] for e in ev]
    assert {"serve_bins", "serve_roundtrip", "serve_route",
            "serve_leaf_sum"} <= set(names)
    (batch,) = [e for e in ev if e[1] == "serve_batch"]
    (leaf_sum,) = [e for e in ev if e[1] == "serve_leaf_sum"]
    assert _inside(leaf_sum, batch)
    assert leaf_sum[6] == {"rows": 300, "trees": 2}


def test_gc_and_compile_spans_only_under_an_enabled_default():
    import gc

    import jax
    import jax.numpy as jnp
    from repro.obs import trace as obs_trace
    tr = Tracer("proc")
    prev = obs_trace.set_default(tr)
    try:
        gc.collect()
        salt = time.perf_counter_ns() % 1000003    # a fresh program
        jax.jit(lambda x: x * 3 + salt)(jnp.arange(5)).block_until_ready()
    finally:
        obs_trace.set_default(prev)
    ev = tr.export_events()
    assert [e for e in ev if e[1] == "gc" and e[2] == "process"
            and e[6]["generation"] == 2]
    assert [e for e in ev if e[1] == "compile" and e[2] == "process"]
    if not prev.enabled:          # the hooks went with the default
        n = len(tr)
        gc.collect()
        salt += 1
        jax.jit(lambda x: x * 5 + salt)(jnp.arange(5)).block_until_ready()
        assert len(tr) == n
        assert obs_trace._PROCESS_SPANS.on_gc not in gc.callbacks


def test_gc_inside_the_tracers_critical_section_is_owed_not_deadlocked():
    """A collection can start on the thread that holds the tracer's lock
    (an allocation inside it triggers one): its span waits for the lock
    to be free instead of taking it."""
    import gc

    from repro.obs import trace as obs_trace
    tr = Tracer("proc")
    prev = obs_trace.set_default(tr)
    try:
        with tr._lock:
            gc.collect()
        owed = len(tr)
    finally:
        obs_trace.set_default(prev)     # hands the owed spans over
    assert owed == 0
    assert [e for e in tr.export_events() if e[1] == "gc"]


@pytest.mark.parametrize("kw", [
    dict(cipher="affine", key_bits=256),
    dict(cipher="affine", key_bits=256, row_block=128),
    dict(cipher="plain", packing=False),
], ids=["affine", "affine-streamed", "plain-nopack"])
def test_inprocess_tracing_off_is_bit_identical(kw):
    """Tracing on or off, in process: the same model, ledger and counts
    (every new span site is observation only), and the disabled tracer
    records nothing."""
    X, y = _data(n=300)
    Xg, Xh = X[:, :3], [X[:, 3:]]
    off = VerticalBoosting(_fit_params(**kw)).fit(Xg, y, Xh)
    on = VerticalBoosting(_fit_params(trace=True, **kw)).fit(Xg, y, Xh)
    assert not off.tracer.enabled and len(off.tracer) == 0
    np.testing.assert_array_equal(on.train_score_, off.train_score_)
    assert on.channel.summary() == off.channel.summary()

    def counts(m):
        return {k: v for k, v in m.stats.as_dict().items()
                if isinstance(v, int)}
    assert counts(on) == counts(off)
    np.testing.assert_array_equal(on.predict_proba(Xg, Xh),
                                  off.predict_proba(Xg, Xh))
