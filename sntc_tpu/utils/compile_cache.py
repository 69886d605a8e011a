"""Persistent XLA compilation cache (SURVEY.md §3.5 cold-start).

Spark pays no per-process compile; JAX pays full XLA compilation on the
first fit of every process (~8-13× the warm fit on the bench configs).
JAX's persistent compilation cache closes most of that gap: compiled
executables are written to a directory keyed by (HLO, flags, platform),
so the SECOND process's "cold" fit only pays trace + cache lookup.

The directory is placed from OUTSIDE the program: where
``JAX_COMPILATION_CACHE_DIR`` is set, exactly that directory is used —
nothing is appended to it and nothing in code rewrites the variable —
so whoever runs the program decides whether a cache outlives the
machine.  Unset, the cache lives at a fixed path inside the checkout
(``<repo>/.jax_cache``, git-ignored), derived from the package's own
location: the path is part of what makes an entry findable again, so it
never comes from ``$HOME``, a temp name, a pid or the time.

Opt-out with ``SNTC_NO_COMPILE_CACHE=1``.

:func:`enable_persistent_cache` also installs the process's compile
listener (cache or no cache): every executable XLA builds or loads from
this cache — every miss of a jitted function's own in-memory cache —
counts into ``sntc_xla_compiles_total{outcome}`` /
``sntc_xla_compile_seconds_total`` and leaves an ``xla.compile`` marker
span, so a compile inside a measured window shows on the trace's clock.
"""

from __future__ import annotations

import os
import threading

from sntc_tpu.obs import inc, module_of, span

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


_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MODULE = module_of(__name__)


class _CompileListener:
    """``jax.monitoring`` listener pair.  jax reports the backend-compile
    duration for an executable it built and for one it loaded from the
    persistent cache alike; a load fires the cache-hit event first, on
    the compiling thread, which is how the two are told apart."""

    def __init__(self):
        self._loaded = threading.local()

    def on_event(self, event: str, **kwargs) -> None:
        if event == _CACHE_HIT_EVENT:
            self._loaded.flag = True

    def on_duration(self, event: str, duration_secs: float, **kwargs) -> None:
        if event != _BACKEND_COMPILE_EVENT:
            return
        loaded = getattr(self._loaded, "flag", False)
        self._loaded.flag = False
        outcome = "cache_loaded" if loaded else "compiled"
        inc("sntc_xla_compiles_total", outcome=outcome)
        inc("sntc_xla_compile_seconds_total", duration_secs)
        # a marker, opened and closed at once where the compile ENDED
        with span("xla.compile", seconds=duration_secs, outcome=outcome,
                  program=kwargs.get("fun_name", ""), module=_MODULE):
            pass


_listener: _CompileListener | None = None


def _install_compile_listener() -> None:
    global _listener
    if _listener is not None:
        return
    import jax.monitoring

    _listener = _CompileListener()
    jax.monitoring.register_event_listener(_listener.on_event)
    jax.monitoring.register_event_duration_secs_listener(
        _listener.on_duration
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
