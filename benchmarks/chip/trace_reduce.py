"""Reduce a ``jax.profiler`` trace of a benchmark window to what the
benchmark reports from it:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the harness's ``window`` span, averaged over the devices
  that ran any; ``window_s``, that span's length; ``idle_share``, one less
  their ratio;
* ``device_ops``: the device operations that took most time in the window,
  ``[name, seconds]``, named ``<program>/<operation>`` (``jit__route/
  while.1``), seconds summed over their runs and averaged over the devices;
* ``idle_gaps``: the longest intervals of the window in which the first
  device ran nothing, ``[name, seconds]``, each named by the innermost host
  span that covers its middle: the harness's own spans from the trace, and
  the program's spans where they are given.

The program's spans run on ``time.perf_counter_ns``, not on the profiler's
clock.  The harness records ``perf_counter_ns`` as it opens each unit's
span, so the median distance between those readings and the same spans in
the trace puts the program's spans on the trace's clock.
"""

from __future__ import annotations

import bisect
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s: float, e: float, w0: float, w1: float):
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


def _host_events(profile) -> list:
    return [(e.name, e.start_ns, e.end_ns)
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _op_name(event_name: str) -> str:
    """``%fusion.3 = s32[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _device_ops(profile) -> list:
    """Per device plane that ran operations: [(name, start, end)], each
    name qualified by the program (XLA module) it ran in."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/device:") \
                or plane.name.startswith("/device:CPU"):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = sorted((e.start_ns, e.end_ns, e.name.split("(", 1)[0])
                         for e in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in modules]
        ops = []
        for e in lines.get(OPS_LINE, []):
            i = bisect.bisect_right(starts, e.start_ns) - 1
            mod = modules[i][2] if i >= 0 and e.start_ns < modules[i][1] \
                else "?"
            ops.append((f"{mod}/{_op_name(e.name)}", e.start_ns, e.end_ns))
        if ops:
            out.append(ops)
    return out


def _median(xs: list) -> float:
    xs = sorted(xs)
    m = len(xs) // 2
    return xs[m] if len(xs) % 2 else 0.5 * (xs[m - 1] + xs[m])


def reduce(xplane, window: str = "window", step: str | None = None,
           step_starts_ns=(), program_events=(), top: int = 10) -> dict:
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(str(xplane))
    host = _host_events(profile)
    spans = [(s, e, name) for name, s, e in host if name == window]
    if not spans:
        raise ValueError(f"no host span named {window!r} in {xplane}")
    w0, w1, _ = max(spans, key=lambda x: x[1] - x[0])
    devices = _device_ops(profile)
    if not devices:
        raise ValueError(f"no device operations in {xplane}")

    busy, totals, first_union = [], {}, None
    for ops in devices:
        clipped = []
        for name, s, e in ops:
            c = _clip(s, e, w0, w1)
            if c:
                clipped.append(c)
                totals[name] = totals.get(name, 0.0) + (c[1] - c[0])
        u = _union(clipped)
        if first_union is None:
            first_union = u
        busy.append(sum(e - s for s, e in u))
    n_dev = len(devices)
    busy_ns = sum(busy) / n_dev
    window_ns = w1 - w0

    named = [(s, e, name) for name, s, e in host
             if _clip(s, e, w0, w1) and e > s]
    if step and step_starts_ns and program_events:
        starts = sorted(s for name, s, _ in host if name == step)
        pairs = list(zip(starts, step_starts_ns))
        if pairs:
            offset = _median([t - p for t, p in pairs])
            named += [(ts + offset, ts + dur + offset, name)
                      for ph, name, _cat, ts, dur, *_ in program_events
                      if ph == "X" and dur > 0]
    gaps, cursor = [], w0
    for s, e in first_union + [[w1, w1]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for s, e in gaps[:top]:
        mid = 0.5 * (s + e)
        cover = [x for x in named if x[0] <= mid <= x[1]]
        name = min(cover, key=lambda x: x[1] - x[0])[2] if cover \
            else "untraced"
        idle.append([name, (e - s) * 1e-9])
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_ns * 1e-9, "window_s": window_ns * 1e-9,
            "idle_share": 1.0 - busy_ns / window_ns,
            "device_ops": [[name, t / n_dev * 1e-9] for name, t in ops],
            "idle_gaps": idle}
