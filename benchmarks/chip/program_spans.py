"""Time per unit of work in the program's own spans, for the per-layer
metrics that read them.

A ``--trace 1`` run installs an enabled process-default tracer
(``repro.obs.trace``) before set-up, and the program records its phases
into it.  The window's units are the last ``len(run.units)`` records the
program made under the unit's name (``round`` for a boosting round,
``serve_batch`` for a served batch).  A span counts toward a unit when it
lies inside the unit's interval on the same thread.

Every function returns ``None`` when the run was not traced, ran no unit,
or the tracer's ring no longer holds a record for each unit of the window
(it overflowed); and when no span of the name it reads lies in any unit,
as with a program that records no such span.
"""

from __future__ import annotations


def _units(run, unit: str):
    """(the window's unit records, every complete event), or None."""
    if run.trace_summary is None or not run.units:
        return None
    from repro.obs import trace as obs_trace
    events = [e for e in obs_trace.current().export_events() if e[0] == "X"]
    records = [e for e in events if e[1] == unit]
    if len(records) < len(run.units):
        return None
    return records[-len(run.units):], events


def _inside(events: list, record) -> list:
    """The events other than ``record`` that lie inside its interval on
    its thread."""
    _, _, _, u0, udur, utid, _ = record
    u1 = u0 + udur
    return [e for e in events if e is not record and e[5] == utid
            and e[3] >= u0 and e[3] + e[4] <= u1]


def seconds_per_unit(run, unit: str, name: str):
    """Seconds per unit spent in spans named ``name``."""
    found = _units(run, unit)
    if found is None:
        return None
    records, events = found
    named = [e for e in events if e[1] == name]
    spans = [e for r in records for e in _inside(named, r)]
    if not spans:
        return None
    return sum(e[4] for e in spans) * 1e-9 / len(records)


def self_seconds_per_unit(run, unit: str):
    """Seconds per unit that no other program span covers: the unit
    record's time less the union of the spans inside it."""
    found = _units(run, unit)
    if found is None:
        return None
    records, events = found
    total = 0
    for r in records:
        covered, end = 0, None
        for s, e in sorted((c[3], c[3] + c[4]) for c in _inside(events, r)):
            if end is None or s > end:
                covered += e - s
                end = e
            elif e > end:
                covered += e - end
                end = e
        total += r[4] - covered
    return total * 1e-9 / len(records)
