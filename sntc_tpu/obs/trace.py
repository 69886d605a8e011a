"""Span tracer — one call site, two sinks.

``obs.span("stage.name", **attrs)`` wraps a stage at a layer boundary.
A span goes to either, both or neither of:

* the **ring**: each closed span records (name, monotonic start,
  duration, wall start, thread id, attrs, id, parent id) onto a bounded
  ring, armed by :func:`enable_tracing` (``--trace-out``);
* the **profiler's trace**: while a ``jax.profiler`` session is running
  (``TraceAnnotation.is_enabled()`` — the session IS the switch) the
  span also opens a ``TraceAnnotation`` named ``sntc:<name>`` with the
  same attributes, so it lands in the ``.xplane.pb`` beside the
  device's operations, on the device trace's clock.

With neither on, the call returns one shared no-op — the hot paths
carry the calls permanently (docs/OBSERVABILITY.md has the costs).

Work that someone else clocked takes one sink each: :func:`interval`
writes a finished interval onto the ring with its true start and length,
:func:`marker` a zero-length ``sntc:<name>`` event into the profiler's
trace (``utils/compile_cache.py``: jax's trace / lower / compile spans).

:meth:`SpanTracer.export_chrome_trace` writes the ring as Chrome
``traceEvents`` JSON, loadable in ``chrome://tracing`` and
https://ui.perfetto.dev — every span a complete ("X") event on its
thread's track.  Ring overflow drops the OLDEST spans and counts them
(``sntc_spans_dropped_total``), never silently.

Device-side correlation hooks (both opt-in — they cost real time):

* :func:`device_trace` — a ``jax.profiler`` session (XLA op-level
  timeline, the program's ``sntc:`` spans on the same clock, no Python
  tracer) around any region; the CLIs expose it as
  ``--device-trace DIR``.
* ``SNTC_OBS_COST_ANALYSIS=1`` — the fusion planner additionally runs
  XLA's compiled-program ``cost_analysis()`` per compiled signature and
  keeps the FLOPs/bytes estimates on the segment
  (``fusion_stats()["cost_analysis"]``), so host spans can be compared
  against what the program *should* cost on the device.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from sntc_tpu.obs.metrics import inc


class _NullSpan:
    """Shared no-op context manager for the tracing-disabled path."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One live span: records itself on exit (exceptions included —
    a failing stage's time is exactly the time worth seeing).
    ``tracer`` is the ring it records onto (or None), ``annotation`` the
    profiler's ``TraceAnnotation`` it holds open meanwhile (or None)."""

    __slots__ = ("_tracer", "_annotation", "name", "attrs", "_t0",
                 "_wall0", "_id", "_parent")

    def __init__(self, tracer: "Optional[SpanTracer]", name: str, attrs,
                 annotation=None):
        self._tracer = tracer
        self._annotation = annotation
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        t = self._tracer
        if t is not None:
            self._id, self._parent = t._open()
            self._wall0 = t._wall()
            self._t0 = t._clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        t = self._tracer
        if t is not None:
            t._record(
                self.name,
                self._t0,
                t._clock() - self._t0,
                self._wall0,
                threading.get_ident(),
                self.attrs,
                self._id,
                self._parent,
            )
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        return False


class SpanTracer:
    """Bounded ring of closed spans (thread-safe; injectable clocks).

    ``capacity`` bounds memory for the life of the process; overflow
    evicts oldest and counts ``dropped``.
    """

    def __init__(
        self,
        capacity: int = 65_536,
        *,
        clock=time.perf_counter,
        wall=time.time,
    ):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        self._clock = clock
        self._wall = wall
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self.dropped = 0
        self._ids = itertools.count(1)
        self._live = threading.local()  # .stack: this thread's open ids

    def span(self, name: str, **attrs: Any) -> _Span:
        return _Span(self, name, attrs or None)

    def _open(self):
        """``(id, parent)`` of a span opening now on this thread: the
        parent is the enclosing live span of the same thread."""
        stack = getattr(self._live, "stack", None)
        if stack is None:
            stack = self._live.stack = []
        sid = next(self._ids)
        parent = stack[-1] if stack else None
        stack.append(sid)
        return sid, parent

    def _record(self, name, t0, dur, wall0, tid, attrs, sid, parent) -> None:
        self._live.stack.pop()
        self._append(name, t0, dur, wall0, tid, attrs, sid, parent)

    def record_interval(self, name: str, wall_start: float,
                        wall_end: float, **attrs: Any) -> None:
        """A finished interval someone else clocked (``jax.monitoring``'s
        time spans, stamped with ``time.time()``), written as it was: the
        ring records at close, so its true start and length go in whole.
        The wall stamps are moved onto the ring's monotonic clock by
        their distance from now; the parent is this thread's live span."""
        stack = getattr(self._live, "stack", None)
        self._append(
            name, self._clock() - (self._wall() - wall_start),
            wall_end - wall_start, wall_start, threading.get_ident(),
            attrs or None, next(self._ids), stack[-1] if stack else None,
        )

    def _append(self, name, t0, dur, wall0, tid, attrs, sid, parent) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
                try:
                    inc("sntc_spans_dropped_total")
                except Exception:
                    pass
            self._ring.append(
                (name, t0, dur, wall0, tid, attrs, sid, parent)
            )

    def spans(self) -> List[Dict[str, Any]]:
        with self._lock:
            ring = list(self._ring)
        return [
            {
                "name": name, "t0": t0, "dur_s": dur, "wall": wall0,
                "tid": tid, "attrs": attrs or {}, "id": sid,
                "parent": parent,
            }
            for name, t0, dur, wall0, tid, attrs, sid, parent in ring
        ]

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "spans": len(self._ring),
                "capacity": self.capacity,
                "dropped": self.dropped,
            }

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0

    def export_chrome_trace(self, path: str) -> str:
        """Write the ring as Chrome trace-event JSON (``ph: "X"``
        complete events, µs timestamps) — loadable in chrome://tracing
        and ui.perfetto.dev.  Atomic publish (tmp + rename)."""
        with self._lock:
            ring = list(self._ring)
        pid = os.getpid()
        thread_names = {
            t.ident: t.name for t in threading.enumerate()
            if t.ident is not None
        }
        events: List[Dict[str, Any]] = []
        for tid, tname in sorted(thread_names.items()):
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid,
                "tid": tid, "args": {"name": tname},
            })
        for name, t0, dur, wall0, tid, attrs, sid, parent in ring:
            ev: Dict[str, Any] = {
                "name": name, "cat": "host", "ph": "X",
                "ts": round(t0 * 1e6, 3),
                "dur": round(dur * 1e6, 3),
                "pid": pid, "tid": tid,
            }
            args = dict(attrs) if attrs else {}
            args["wall_ts"] = wall0
            args["id"] = sid
            args["parent"] = parent
            ev["args"] = args
            events.append(ev)
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "tool": "sntc_tpu.obs",
                "dropped_spans": self.dropped,
            },
        }
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)  # storage: telemetry
        return path


# ---------------------------------------------------------------------------
# the process tracer: disabled (None) by default; span() is the
# permanent hot-path call site
# ---------------------------------------------------------------------------

_tracer: Optional[SpanTracer] = None


def _session_annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler session is
    running, else None.  ``jax`` is taken from ``sys.modules``: a process
    that never imported it has no session, and this package imports
    only the standard library."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    annotation = profiler.TraceAnnotation
    return annotation if annotation.is_enabled() else None


def span(name: str, **attrs: Any):
    """``with obs.span("stream.read", batch=3): ...`` — records onto
    the process tracer's ring when armed, into the profiler's trace (as
    ``sntc:<name>``) while a session runs; a shared no-op otherwise."""
    t = _tracer
    annotation = _session_annotation()
    if annotation is None:
        if t is None:
            return _NULL_SPAN
        return t.span(name, **attrs)
    return _Span(t, name, attrs or None, annotation("sntc:" + name, **attrs))


def interval(name: str, wall_start: float, wall_end: float,
             **attrs: Any) -> None:
    """A finished interval onto the ring when it is armed
    (:meth:`SpanTracer.record_interval`); nothing otherwise, and nothing
    into the profiler's trace, which takes no event after the fact."""
    t = _tracer
    if t is not None:
        t.record_interval(name, wall_start, wall_end, **attrs)


def marker(name: str, **attrs: Any) -> None:
    """A zero-length ``sntc:<name>`` event in the profiler's trace while
    a session runs, at this instant on the device trace's clock; nothing
    otherwise, and nothing onto the ring."""
    annotation = _session_annotation()
    if annotation is not None:
        with annotation("sntc:" + name, **attrs):
            pass


def module_of(where) -> str:
    """The layer a span's ``module=`` attribute names: the second part
    of a module path (``sntc_tpu.feature.chisq_selector`` ->
    ``feature``), of a dotted name or of the class or function given."""
    path = where if isinstance(where, str) else where.__module__
    return path.split(".")[1] if "." in path else path


def tracer() -> Optional[SpanTracer]:
    return _tracer


def tracing_enabled() -> bool:
    return _tracer is not None


def enable_tracing(capacity: int = 65_536, **kwargs: Any) -> SpanTracer:
    """Arm the process tracer (idempotent: an already-armed tracer is
    returned unchanged unless a new capacity is requested)."""
    global _tracer
    if _tracer is None or _tracer.capacity != capacity:
        _tracer = SpanTracer(capacity, **kwargs)
    return _tracer


def disable_tracing() -> Optional[SpanTracer]:
    """Disarm and return the tracer (its ring stays readable)."""
    global _tracer
    t, _tracer = _tracer, None
    return t


# ---------------------------------------------------------------------------
# device-side correlation (opt-in)
# ---------------------------------------------------------------------------


class device_trace:
    """``with device_trace(log_dir):`` — a ``jax.profiler`` session
    around the block: the device's operations and, on the same clock,
    every ``obs.span`` opened inside it (``sntc:<name>`` host events).
    The Python tracer is off (it would record every Python call) and
    the host tracer at level 2, so the ``.xplane.pb`` under
    ``log_dir/plugins/profile/`` is the kind of trace the benchmark
    reduces.  The CLIs gate it behind ``--device-trace DIR``."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def __enter__(self):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False
