"""What a process spent before its first model, by the program's own count.

``setup_s`` is the harness's: process start to window start.  The program
counts its parts itself, always on, into its metrics registry
(``sntc_tpu.obs.registry()``, this process's):

* ``sntc_process_device_ready_seconds``: process start (the kernel's) to the
  first mesh (``parallel/mesh.py``);
* ``sntc_pipeline_first_fit_seconds``: the first ``Pipeline.fit``'s wall
  seconds (``core/base.py``);
* ``sntc_xla_trace_seconds_total{program}``,
  ``sntc_xla_lower_seconds_total{program}``,
  ``sntc_xla_compile_seconds_total{outcome, program}`` and
  ``sntc_xla_compiles_total{outcome}``: each first call's tracing, lowering
  and backend compile (``outcome="compiled"``) or load from the persistent
  cache (``outcome="cache_loaded"``), every span's own seconds, so they add
  up to wall seconds (``utils/compile_cache.py``).

The readers under ``layer_metrics/`` (``device_ready_s``, ``first_fit_s``,
``first_call_s.*``, ``first_call_programs``, ``first_call_compiled``) take the
registry as it stands when the harness reads its per-layer metrics: after the
traced window and before the reference is compared, so the reference's own
compiles are not in it; ``compiles_in_window.fit`` 0 says none of it fell
inside the window.  A registry without a series (a program from before it
existed) gives ``None``, never 0; a process that has the labelled counters
and met one outcome only reads 0 seconds for the other, so a cell's line
carries all eight whatever the cache held.
"""

from __future__ import annotations

import sys

#: the registry folds label sets past its cap into this one series; its
#: seconds belong to the sum, and it carries no other label
OVERFLOW = {"overflow": "true"}
TOP = 5


def series(name: str):
    """``[(labels, value)]`` of one counter or gauge, or None when the
    program has not written it (or has no such metric)."""
    from sntc_tpu.obs import registry

    metric = registry().snapshot().get(name)
    if metric is None:
        return None
    return [(row["labels"], row["value"]) for row in metric["series"]]


def gauge(name: str):
    rows = series(name)
    return None if not rows else float(rows[0][1])


def _say(phase: str, rows) -> float:
    """The rows' sum, and to standard error the ``TOP`` programs with the
    most seconds of the phase (read, not compared)."""
    value = float(sum(v for _, v in rows))
    rows = sorted(rows, key=lambda row: -row[1])[:TOP]
    most = ", ".join(f"{labels.get('program', '(overflow)')} {v:.3f}"
                     for labels, v in rows)
    print(f"first call, {phase}: {value:.3f} s; most: {most}",
          file=sys.stderr, flush=True)
    return value


def phase_seconds(name: str, phase: str):
    """A phase's seconds summed over ``program``, the overflow series
    with them; None when the program has no such counter."""
    rows = series(name)
    return _say(phase, rows) if rows else None


def compiles(outcome: str | None = None):
    """Executables the process built (``compiled``), loaded
    (``cache_loaded``) or both (None).  A process whose listener has
    counted anything gives 0 for the outcome it has not met; one that
    has counted nothing gives None."""
    rows = series("sntc_xla_compiles_total")
    if not rows:
        return None
    return float(sum(v for labels, v in rows
                     if outcome in (None, labels.get("outcome"))))


def compile_seconds(outcome: str, phase: str):
    """``sntc_xla_compile_seconds_total`` of one outcome, summed over
    ``program``.  A process that has met only the other outcome gives 0 (a
    warm cache builds nothing, and the cell's line still has to carry the
    metric); None when no series carries an ``outcome`` at all (a program
    whose counter has no such label, or one that has counted nothing).
    The overflow series carries no outcome: its seconds go to the only
    outcome the process has met (``sntc_xla_compiles_total`` has two label
    sets and never folds), and to ``compiled`` in a process that met both."""
    rows = series("sntc_xla_compile_seconds_total") or ()
    if not any("outcome" in labels for labels, _ in rows):
        return None
    own = [(labels, v) for labels, v in rows
           if labels.get("outcome") == outcome]
    if not own:
        return 0.0
    other = "cache_loaded" if outcome == "compiled" else "compiled"
    if outcome == "compiled" or not compiles(other):
        own += [(labels, v) for labels, v in rows if labels == OVERFLOW]
    return _say(phase, own)
