"""Structured tracing: bounded ring-buffer span/instant events (DESIGN.md §14).

A :class:`Tracer` records events into a bounded per-process ring buffer
(`collections.deque(maxlen=capacity)` — overflow drops the OLDEST events
and counts them, never blocks the hot path).  Timestamps come from
``time.perf_counter_ns()``: the same monotonic clock as
``time.perf_counter()``, so code that already measured a phase with
float ``perf_counter()`` deltas can re-emit the interval exactly via
:meth:`Tracer.complete` with ``int(t * 1e9)``.

An enabled span also opens a ``jax.profiler.TraceAnnotation`` of the same
name, so it lies on the host plane of any ``jax.profiler`` trace, on the
profiler's clock, beside the device's operations.  The names in
:data:`RESERVED` belong to a benchmark harness that pairs its own
annotations of them with its own clock readings: :meth:`Tracer.span`
refuses them, and the program records them with :meth:`Tracer.complete`,
which opens no annotation.

Zero-cost-when-disabled is the design contract: every emission site is
guarded by ``tracer.enabled`` (one attribute load + bool test), and the
module-level :data:`NULL_TRACER` singleton answers ``span()`` with a
shared no-op context manager, so disabled tracing adds no allocation,
no lock, no clock read, no annotation.  :meth:`Tracer.timed` is the span
of a phase that also feeds a ``Stats`` timer: disabled, it still reads
the clock (the timer needs it) and records nothing.

While an enabled tracer is the process default (:func:`set_default`),
every garbage collection (``gc.callbacks``) and every executable JAX
builds (the ``jax.monitoring`` duration event of a backend compile,
ending when the event arrives) is recorded into it as a ``gc`` or
``compile`` span, category ``process``.

Event tuples are ``(ph, name, cat, ts_ns, dur_ns, tid, attrs)`` with
``ph`` the Chrome-trace phase ("X" complete, "i" instant).  ``attrs``
must stay codec-serializable (str/int/float/bool) — host buffers cross
the wire over the ``trace_sync`` control tag.

Categories in use: ``train`` (round/tree/layer/encrypt/...), ``wire``
(one instant per :meth:`Channel.send` ledger append — the audited
category), ``transport`` (framed ship/recv/broker/retry — physical, NOT
audited, so the two views never double count), ``chaos``, ``serve``,
``process`` (gc/compile).
"""

from __future__ import annotations

import gc
import threading
import time
from collections import deque

# Names a benchmark harness annotates itself (its window, its unit of work,
# its reference check): the program never opens a profiler annotation
# under them.
RESERVED = frozenset({"window", "round", "serve_batch", "reference"})

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _NullSpan:
    """Shared no-op context manager for disabled tracers."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()


class _Timer:
    """One clock-read pair; ``seconds`` after the block."""
    __slots__ = ("start_ns", "dur_ns")

    def __enter__(self):
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur_ns = time.perf_counter_ns() - self.start_ns
        return False

    @property
    def seconds(self) -> float:
        return self.dur_ns * 1e-9


class _Span(_Timer):
    __slots__ = ("tracer", "name", "cat", "tid", "attrs", "annotation")

    def __init__(self, tracer, name, cat, tid, attrs):
        if name in RESERVED:
            raise ValueError(f"span name {name!r} is reserved for the "
                             f"benchmark harness; record it with complete()")
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.tid = tid
        self.attrs = attrs

    def __enter__(self):
        from jax.profiler import TraceAnnotation
        self.annotation = TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur_ns = time.perf_counter_ns() - self.start_ns
        self.annotation.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        self.tracer._emit("X", self.name, self.cat, self.start_ns,
                          self.dur_ns, self.tid, self.attrs)
        return False


class Tracer:
    """Thread-safe bounded event recorder for one party/process."""

    def __init__(self, party: str = "proc", capacity: int = 1 << 16,
                 enabled: bool = True):
        self.party = party
        self.capacity = capacity
        self.enabled = enabled
        self._events: deque = deque(maxlen=capacity)
        self._emitted = 0
        self._lock = threading.Lock()

    # -- emission -------------------------------------------------------
    def _emit(self, ph, name, cat, ts_ns, dur_ns, tid, attrs):
        if tid is None:
            tid = threading.get_ident() & 0x7FFFFFFF
        with self._lock:
            self._emitted += 1
            self._events.append((ph, name, cat, ts_ns, dur_ns, tid, attrs))

    def span(self, name: str, cat: str = "train", tid=None, **attrs):
        """``with tracer.span("layer", tree=t, depth=d): ...``"""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, cat, tid, attrs)

    def timed(self, name: str, cat: str = "train", tid=None, **attrs):
        """A span whose ``seconds`` feed a ``Stats`` timer: the same
        clock-read pair times the phase whether or not it is recorded."""
        if not self.enabled:
            return _Timer()
        return _Span(self, name, cat, tid, attrs)

    def instant(self, name: str, cat: str = "train", tid=None, **attrs):
        if not self.enabled:
            return
        self._emit("i", name, cat, time.perf_counter_ns(), 0, tid, attrs)

    def complete(self, name: str, start_ns: int, dur_ns: int,
                 cat: str = "train", tid=None, **attrs):
        """Emit an already-measured interval (reuses existing
        ``perf_counter()`` floats: pass ``int(t0 * 1e9)``)."""
        if not self.enabled:
            return
        self._emit("X", name, cat, int(start_ns), max(int(dur_ns), 0),
                   tid, attrs)

    # -- introspection --------------------------------------------------
    @property
    def dropped(self) -> int:
        with self._lock:
            return self._emitted - len(self._events)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def export_events(self) -> list:
        """Codec-serializable snapshot: list of 7-element lists."""
        with self._lock:
            return [[ph, name, cat, ts, dur, tid, dict(attrs)]
                    for ph, name, cat, ts, dur, tid, attrs in self._events]

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._emitted = 0


NULL_TRACER = Tracer(party="null", capacity=1, enabled=False)

# Process-default tracer: emission sites with no Channel in reach (chaos
# endpoints wrap the transport BEFORE the channel exists, fault-layer
# events, benchmark harness).  Per-party attribution everywhere else
# rides on the explicit ``Channel.tracer`` attribute instead, so the
# loopback single-process mode still attributes guest vs host correctly.
_default: Tracer = NULL_TRACER


def set_default(tracer: Tracer) -> Tracer:
    global _default
    _PROCESS_SPANS.flush()          # what the outgoing default is owed
    prev = _default
    _default = tracer if tracer is not None else NULL_TRACER
    if _default.enabled:
        _PROCESS_SPANS.install()
    else:
        _PROCESS_SPANS.remove()
    return prev


def current() -> Tracer:
    return _default


class _ProcessSpans:
    """``gc`` and ``compile`` spans into the process-default tracer,
    hooked in only while an enabled tracer is the default."""

    def __init__(self):
        self.installed = False
        self._gc_open = None        # (start_ns, annotation) of a collection
        self._owed: deque = deque()  # spans not yet in the default's ring

    def install(self) -> None:
        if self.installed:
            return
        import jax.monitoring
        gc.callbacks.append(self.on_gc)
        jax.monitoring.register_event_duration_secs_listener(self.on_compile)
        self.installed = True

    def remove(self) -> None:
        if not self.installed:
            return
        import jax.monitoring
        gc.callbacks.remove(self.on_gc)
        jax.monitoring.unregister_event_duration_listener(self.on_compile)
        self.installed = False
        self._gc_open = None

    def flush(self) -> None:
        """Move the owed spans into the default's ring.  A collection can
        run inside the tracer's own critical section, on the thread that
        holds its lock (an allocation there triggers it): taking the lock
        then would never return, so while it is held the spans stay owed
        until the next chance."""
        tr = _default
        while self._owed and not tr._lock.locked():
            name, start_ns, dur_ns, attrs = self._owed.popleft()
            tr.complete(name, start_ns, dur_ns, cat="process", **attrs)

    def on_gc(self, phase: str, info: dict) -> None:
        # collections never overlap: the interpreter runs one at a time
        if phase == "start":
            from jax.profiler import TraceAnnotation
            annotation = TraceAnnotation("gc")
            annotation.__enter__()
            self._gc_open = (time.perf_counter_ns(), annotation)
        elif self._gc_open is not None:
            start_ns, annotation = self._gc_open
            self._gc_open = None
            dur_ns = time.perf_counter_ns() - start_ns
            annotation.__exit__(None, None, None)
            self._owed.append(("gc", start_ns, dur_ns,
                               {"generation": int(info["generation"]),
                                "collected": int(info["collected"])}))
            self.flush()

    def on_compile(self, event: str, secs: float, **kw) -> None:
        if event != COMPILE_EVENT:
            return
        dur_ns = int(secs * 1e9)
        self._owed.append(("compile", time.perf_counter_ns() - dur_ns,
                           dur_ns, {"fun": str(kw.get("fun_name", ""))}))
        self.flush()


_PROCESS_SPANS = _ProcessSpans()
