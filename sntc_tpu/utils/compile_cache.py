"""Persistent XLA compilation cache (SURVEY.md §3.5 cold-start).

Spark pays no per-process compile; JAX pays full XLA compilation on the
first fit of every process.  On the chip (one TPU v5e, the benchmark's
forest cell, 4,058,236 x 78 rows; PERF.md §5 "Where set-up goes", PR 37)
a process's first fit takes 40.0 s from an empty cache against 8.0 s
for a later one: 23.4 s of it building 17 executables (17.8 s for
``_grow_fused`` alone).  JAX's persistent compilation cache closes that
gap: compiled executables are written to a directory keyed by (HLO,
flags, platform), so the SECOND process's first fit takes 16.7 s: it
loads the 17 in 0.5 s and still pays 2.2 s of tracing, 1.9 s of lowering
and 3.7 s of imports the fit makes on its way.  The key of a program
that holds a Pallas kernel carries the kernel's source locations, so a
checkout at another path builds those programs again (PERF.md §7 (x)).

The directory is placed from OUTSIDE the program: where
``JAX_COMPILATION_CACHE_DIR`` is set, exactly that directory is used —
nothing is appended to it and nothing in code rewrites the variable —
so whoever runs the program decides whether a cache outlives the
machine.  Unset, the cache lives at a fixed path inside the checkout
(``<repo>/.jax_cache``, git-ignored), derived from the package's own
location: the path is part of what makes an entry findable again, so it
never comes from ``$HOME``, a temp name, a pid or the time.

Opt-out with ``SNTC_NO_COMPILE_CACHE=1``.

:func:`enable_persistent_cache` also installs the process's first-call
listener (cache or no cache).  jax reports the three phases of every
miss of a jitted function's own in-memory cache as time spans: tracing,
lowering, and the backend compile (a build, or a load from this cache).
The listener counts each span's own seconds into
``sntc_xla_trace_seconds_total{program}``,
``sntc_xla_lower_seconds_total{program}`` and
``sntc_xla_compile_seconds_total{outcome, program}`` and one executable
into ``sntc_xla_compiles_total{outcome}``; with the span ring armed it
writes ``xla.trace`` / ``xla.lower`` / ``xla.compile`` intervals, and in
a profiler's trace an ``xla.compile`` marker, so a compile inside a
measured window shows on the trace's clock.
"""

from __future__ import annotations

import os
import threading
from collections import deque

from sntc_tpu.obs import inc, interval, marker, module_of

#: the in-checkout default: <repo>/.jax_cache, beside the package
_DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def resolve_cache_dir() -> str | None:
    """The directory the cache uses, without touching jax.config: the
    ``JAX_COMPILATION_CACHE_DIR`` value verbatim when set, else the
    fixed in-checkout default; None when the cache is disabled."""
    if os.environ.get("SNTC_NO_COMPILE_CACHE"):
        return None
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_DIR


def fsck_compile_cache(
    cache_dir: str | None = None, *, repair: bool = True
) -> dict:
    """Doctor the persistent XLA compilation cache (r18, the ``sntc
    fsck`` extension): a crash or ENOSPC mid-write can leave
    zero-length, unreadable, or orphaned-tmp entries under the
    directory :func:`enable_persistent_cache` manages — jax then either
    warns per hit or, in the worst case, dies deserializing a torn
    executable.  Poisoned entries are QUARANTINED to ``.corrupt/``
    beside the cache (the r17 ``.corrupt/`` discipline — evidence
    preserved, never deleted) so the next compile is a clean miss that
    RECOMPILES instead of crashing; ``*.tmp`` orphans are swept.

    Cache entries are opaque compressed executables, so "verify" means
    structural health: readable, non-empty, not a tmp orphan — content
    validity stays jax's job (a quarantined entry costs one recompile,
    which is exactly the safe outcome).

    Returns a machine-readable report mirroring the storage-plane fsck
    shape; ``repair=False`` reports without moving anything."""
    resolved = cache_dir or resolve_cache_dir()
    report: dict = {
        "cache_dir": resolved,
        "repair": bool(repair),
        "checked": 0,
        "quarantined": [],
        "cleaned": [],
        "errors": [],
        "ok": True,
    }
    if resolved is None or not os.path.isdir(resolved):
        return report

    def _quarantine(path: str, detail: str) -> None:
        entry = {"path": path, "detail": detail}
        if not repair:
            report["errors"].append(entry)
            return
        # the storage plane's shared quarantine: .corrupt/ beside the
        # blob + a journaled repair record (storage_repair.jsonl under
        # the cache dir) — 'quarantine' means one thing repo-wide
        from sntc_tpu.resilience.storage import quarantine_blob

        dest = quarantine_blob(
            path, artifact="compile_cache", detail=detail,
            root=resolved,
        )
        if dest is None:
            report["errors"].append(
                dict(entry, detail=f"{detail}; quarantine failed")
            )
            return
        entry["quarantined_to"] = dest
        report["quarantined"].append(entry)

    for dirpath, dirs, files in os.walk(resolved):
        dirs[:] = [d for d in dirs if d != ".corrupt"]
        for name in files:
            if name.startswith("storage_repair.jsonl"):
                continue  # the quarantine journal, not a cache entry
            path = os.path.join(dirpath, name)
            stem, _, suffix = name.rpartition(".tmp")
            if stem and (not suffix or suffix.lstrip("-").isdigit()):
                # an orphaned atomic-write temp: a cache writer died
                # mid-publish; the entry it was building never existed
                report["checked"] += 1
                if repair:
                    try:
                        os.unlink(path)
                        report["cleaned"].append({"path": path})
                    except OSError as e:
                        report["errors"].append(
                            {"path": path,
                             "detail": f"unlink failed: {e}"}
                        )
                else:
                    report["errors"].append(
                        {"path": path, "detail": "orphaned tmp file"}
                    )
                continue
            report["checked"] += 1
            try:
                size = os.path.getsize(path)
                if size == 0:
                    _quarantine(path, "zero-length cache entry")
                    continue
                # readable? (permission damage / torn inode both
                # surface here) — one short read, not a full load
                with open(path, "rb") as f:
                    f.read(64)
            except OSError as e:
                _quarantine(path, f"unreadable cache entry: {e}")
    report["ok"] = not report["errors"]
    return report


_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MODULE = module_of(__name__)
#: a first call's phases, as jax names them: the span and the counter of each
_PHASES = {
    _TRACE_EVENT: ("xla.trace", "sntc_xla_trace_seconds_total"),
    _LOWER_EVENT: ("xla.lower", "sntc_xla_lower_seconds_total"),
    _BACKEND_COMPILE_EVENT: (
        "xla.compile", "sntc_xla_compile_seconds_total"),
}
#: closed spans a thread keeps for an encloser still to come: an outer
#: trace with more inner ones than this in a row counts the oldest twice
_MAX_CLOSED = 4096


class _CompileListener:
    """``jax.monitoring`` listener pair.  jax reports each phase of a
    first call (trace, lower, backend compile) as a time span when it
    ENDS, with ``time.time()`` at its start and end and the program's
    name; it reports the backend compile for an executable it built and
    for one it loaded from the persistent cache alike, and a load fires
    the cache-hit event first, on the compiling thread, which is how the
    two are told apart.

    Spans nest: an inner ``jit`` is traced inside the outer one's trace,
    a lowering rule traces (and an eager op on a constant compiles)
    inside a lowering.  The seconds counted are each span's OWN: an
    instant of a thread goes to the innermost span that covers it,
    whatever the phases, so the three counters add up to wall seconds."""

    def __init__(self):
        self._thread = threading.local()  # .loaded, .closed

    def on_event(self, event: str, **kwargs) -> None:
        if event == _CACHE_HIT_EVENT:
            self._thread.loaded = True

    def _own_seconds(self, start: float, end: float) -> float:
        """``end - start`` less the spans of this thread that closed
        inside it (each taken whole: what closed inside THEM is already
        off their own count)."""
        closed = getattr(self._thread, "closed", None)
        if closed is None:
            closed = self._thread.closed = deque(maxlen=_MAX_CLOSED)
        inner = 0.0
        while closed and closed[-1][0] >= start:
            inner += closed.pop()[1]
        closed.append((start, end - start))
        return max(end - start - inner, 0.0)

    def on_time_span(self, event: str, start_time: float, end_time: float,
                     **kwargs) -> None:
        phase = _PHASES.get(event)
        if phase is None:
            return
        name, counter = phase
        labels = {"program": kwargs.get("fun_name", "")}
        if event == _BACKEND_COMPILE_EVENT:
            loaded = getattr(self._thread, "loaded", False)
            self._thread.loaded = False
            labels["outcome"] = "cache_loaded" if loaded else "compiled"
            inc("sntc_xla_compiles_total", outcome=labels["outcome"])
            # in the profiler's trace a marker, where the compile ENDED
            marker(name, seconds=end_time - start_time, module=_MODULE,
                   **labels)
        inc(counter, self._own_seconds(start_time, end_time), **labels)
        interval(name, start_time, end_time, module=_MODULE, **labels)


_listener: _CompileListener | None = None


def _install_compile_listener() -> None:
    global _listener
    if _listener is not None:
        return
    import jax.monitoring

    _listener = _CompileListener()
    jax.monitoring.register_event_listener(_listener.on_event)
    jax.monitoring.register_event_time_span_listener(
        _listener.on_time_span
    )


def enable_persistent_cache() -> str | None:
    """Turn on JAX's on-disk compilation cache at
    :func:`resolve_cache_dir`; returns the dir (or None when disabled).
    Safe to call more than once; must run before the first compilation
    to help.  Never writes ``JAX_COMPILATION_CACHE_DIR``.  Installs the
    compile listener first, so a process without a cache still counts
    its compiles."""
    _install_compile_listener()
    resolved = resolve_cache_dir()
    if resolved is None:
        return None
    import jax

    os.makedirs(resolved, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", resolved)
    # default min compile time is 1s, which skips most of the small
    # per-stage programs (binning, scaler aggregates) whose compiles
    # still add up across a pipeline; cache everything non-trivial
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return resolved
