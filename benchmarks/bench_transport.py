"""Multi-host transport benchmark (DESIGN.md §10): socket-vs-ledger bytes
and round-trip latency for one training round and one serving batch.

Runs the forced-2-process runtime (guest here, one host spawned over the
length-prefixed localhost socket) and reports, per phase:

* ``ledger_bytes``  — the analytic protocol-fidelity wire model the paper's
  cost equations (10/16) read,
* ``socket_bytes``  — framed bytes that actually crossed the transport
  (tx + rx, headers and the int32 in-memory limb layout included),
* ``overhead_x``    — socket / ledger (the serialization-fidelity gap),
* ``rt_ms``         — median control-frame round-trip latency,
* ``bit_identical`` — vs the in-process Channel oracle.

The host party is a spawned process whose JAX runs on the CPU
(``runtime/transport.py`` ``HOST_PLATFORM``), so it never races this
process for an accelerator.  A socket run that cannot start raises: the
rows always measure the socket transport.
"""

from __future__ import annotations

import tempfile
import time

import numpy as np

from .common import emit, timed

from repro.core import SBTParams, VerticalBoosting
from repro.data import synthetic_tabular
from repro.runtime.transport import MultiHostRun

SHAPE = dict(n=4096, d=12, n_bins=16, max_depth=4)


def _phase_bytes(channel, tags) -> tuple:
    ledger = sum(channel.totals[t] for t in tags)
    sock = sum(channel.tx_bytes[t] + channel.rx_bytes[t] for t in tags)
    return ledger, sock


def main(quick: bool = False):
    s = SHAPE
    n = 1024 if quick else s["n"]
    X, y = synthetic_tabular(n, s["d"], seed=0, task="binary")
    Xg, Xh = X[:, :4], X[:, 4:]
    params = SBTParams(n_trees=1, max_depth=s["max_depth"],
                       n_bins=s["n_bins"], cipher="affine", key_bits=256,
                       precision=20, seed=1)

    ref = VerticalBoosting(params).fit(Xg, y, [Xh])

    rows = []
    run = MultiHostRun(params, [Xh], transport="socket",
                       export_dir=tempfile.mkdtemp(), timeout=300.0)
    try:
        # -- one training round (1 tree) over the transport -------------
        model, t_fit = timed(lambda: run.fit(Xg, y))
        train_tags = ("enc_gh", "assign_sync", "split_infos", "chosen_sid",
                      "assign_mask")
        ledger, sock = _phase_bytes(run.channel, train_tags)
        ident = bool(np.array_equal(model.train_score_, ref.train_score_))
        pings = sorted(run.ping() for _ in range(5))
        rt_ms = pings[len(pings) // 2] * 1e3
        rows.append((
            "transport/train_round",
            t_fit * 1e6,
            f"mode=socket;ledger_bytes={ledger};socket_bytes={sock};"
            f"overhead_x={sock / max(ledger, 1):.2f};rt_ms={rt_ms:.3f};"
            f"roundtrips={model.stats.n_split_roundtrips};"
            f"bit_identical={ident}"))

        # -- one serving batch from reloaded per-party exports -----------
        run.serve()
        ref.predict_score(Xg, [Xh])              # warm the oracle's jits
        base = dict(run.channel.totals)
        base_tx = dict(run.channel.tx_bytes)
        base_rx = dict(run.channel.rx_bytes)
        t0 = time.perf_counter()
        score = run.predict_score(Xg, staged=True)
        t_serve = time.perf_counter() - t0
        serve_tags = ("predict_req", "predict_bits")
        ledger = sum(run.channel.totals[t] - base.get(t, 0)
                     for t in serve_tags)
        sock = sum(run.channel.tx_bytes[t] - base_tx.get(t, 0)
                   + run.channel.rx_bytes[t] - base_rx.get(t, 0)
                   for t in serve_tags)
        s_ref = ref.predict_score(Xg, [Xh])
        rows.append((
            "transport/serve_batch",
            t_serve * 1e6,
            f"mode=socket;rows={n};ledger_bytes={ledger};"
            f"socket_bytes={sock};overhead_x={sock / max(ledger, 1):.2f};"
            f"batch_ms={t_serve * 1e3:.1f};"
            f"bit_identical={bool(np.array_equal(score, s_ref))}"))
    finally:
        run.close()

    rows += _bench_resilience(params, Xg, Xh, y, ref, quick)
    rows += _bench_trace_overhead(params, Xg, Xh, y, quick)
    emit(rows)
    return rows


def _bench_trace_overhead(params, Xg, Xh, y, quick: bool):
    """``transport/trace_overhead`` — the observability layer's cost when
    ENABLED: paired fits with ``trace=True`` vs ``trace=False`` (min of 3
    each), plus the merged event count.  The acceptance bound for the
    DISABLED path is bit-identity + ≤2% (tests/test_obs.py); this row
    tracks what turning tracing ON costs."""
    import dataclasses

    def one_fit(trace: bool) -> tuple:
        p = dataclasses.replace(params, trace=trace)
        run = MultiHostRun(p, [Xh], transport="loopback",
                           export_dir=tempfile.mkdtemp())
        try:
            t0 = time.perf_counter()
            run.fit(Xg, y)
            dt = time.perf_counter() - t0
            n_ev = len(run.trace()) if trace else 0
            return dt, n_ev
        finally:
            run.close()

    try:
        reps = 2 if quick else 3
        one_fit(False)                           # warm jits
        t_off = min(one_fit(False)[0] for _ in range(reps))
        pairs = [one_fit(True) for _ in range(reps)]
        t_on = min(dt for dt, _ in pairs)
        n_ev = pairs[0][1]
        return [(
            "transport/trace_overhead",
            t_on * 1e6,
            f"plain_us={t_off * 1e6:.0f};"
            f"overhead_pct={(t_on / t_off - 1) * 100:.1f};"
            f"events={n_ev}")]
    except Exception as e:                       # noqa: BLE001
        return [("transport/trace_overhead", 0.0,
                 f"skipped={type(e).__name__}")]


def _bench_resilience(params, Xg, Xh, y, ref, quick: bool):
    """Fault-tolerance rows (DESIGN.md §11):

    * ``transport/resilient_overhead`` — the seq/retry/snapshot layer's
      zero-fault cost: a resilient fit with NO faults injected, compared
      against the plain fit wall-clock from the same process (must stay
      within a few percent — the acceptance bound is 5%);
    * ``transport/crash_recovery`` — wall-clock for a fit that takes one
      deterministic mid-tree host kill, minus the fault-free fit: the
      price of detect + respawn + resume, with bit-identity checked.
    """
    import os

    from repro.runtime.chaos import RECV, FaultPlan, Kill

    rows = []

    def one_fit(fault: bool, resilient: bool):
        base = tempfile.mkdtemp()
        plans = {0: FaultPlan(rules=[Kill(tree=0, layer=1, direction=RECV)],
                              seed=5)} if fault else None
        run = MultiHostRun(params, [Xh], transport="socket",
                           export_dir=os.path.join(base, "export"),
                           state_dir=os.path.join(base, "state"),
                           fault_plans=plans, timeout=300.0)
        try:
            t0 = time.perf_counter()
            if resilient:
                model = run.fit(Xg, y, resilient=True,
                                ckpt_dir=os.path.join(base, "ckpt"),
                                max_retries=5)
            else:
                model = run.fit(Xg, y)
            dt = time.perf_counter() - t0
            ident = bool(np.array_equal(model.train_score_,
                                        ref.train_score_))
            return dt, ident, run.restarts
        finally:
            run.close()

    t_plain, _, _ = one_fit(fault=False, resilient=False)
    t_resil, ident, _ = one_fit(fault=False, resilient=True)
    rows.append((
        "transport/resilient_overhead",
        t_resil * 1e6,
        f"plain_us={t_plain * 1e6:.0f};"
        f"overhead_pct={(t_resil / t_plain - 1) * 100:.1f};"
        f"bit_identical={ident}"))

    t_crash, ident, restarts = one_fit(fault=True, resilient=True)
    rows.append((
        "transport/crash_recovery",
        t_crash * 1e6,
        f"faultfree_us={t_resil * 1e6:.0f};"
        f"recovery_cost_us={(t_crash - t_resil) * 1e6:.0f};"
        f"restarts={restarts};bit_identical={ident}"))
    return rows


if __name__ == "__main__":
    main()
