"""Unified telemetry substrate — one metrics/trace/event plane.

Seven PRs of infrastructure each grew an ad-hoc ledger: the engine's
``_emit`` event stream, the compile ledger (``compile_events`` /
``recompiles_after_warmup``), the process ``TransferLedger``,
``prefetch_stats()``, the shed journal, and ``HealthMonitor.snapshot``.
This package is the single plane they all land on:

* :mod:`sntc_tpu.obs.metrics` — a process-wide :class:`MetricsRegistry`
  (counters, gauges, fixed-bucket histograms; label support including
  ``tenant=<id>``) with lock-free-on-read snapshots, Prometheus-text
  and JSONL exposition, and injectable clocks for deterministic tests.
* :mod:`sntc_tpu.obs.trace` — a span tracer (``obs.span("stage",
  **attrs)``) with two sinks: a ring buffer of wall+monotonic intervals
  exported as Chrome-trace/Perfetto JSON, and, while a ``jax.profiler``
  session runs (``obs.device_trace``), the profiler's own trace, where
  the spans sit on the device's clock beside its operations.
* :mod:`sntc_tpu.obs.bridge` — the consolidation glue: an event-stream
  observer folding every structured resilience event (retry, breaker,
  shed, quarantine, drift, health transitions, fault injections) into
  named registry metrics, so the EXISTING emitters need no changes and
  the existing APIs (``transfer_ledger()``, ``recompiles_after_
  warmup()``, ``events_dropped()``) remain thin views over the same
  numbers.

Metric names, label conventions, and the trace-viewer howto live in
``docs/OBSERVABILITY.md``; ``scripts/check_metric_names.py`` pins the
code ⇔ catalog ⇔ docs mapping in tier-1.

This package imports only the standard library at import time, so every
layer (resilience, serve, fuse, utils) can depend on it without cycles.
"""

from sntc_tpu.obs.bridge import install_event_metrics
from sntc_tpu.obs.metrics import (
    CATALOG,
    MetricsRegistry,
    inc,
    observe,
    registry,
    reset_registry,
    set_gauge,
    set_registry,
)
from sntc_tpu.obs.trace import (
    SpanTracer,
    device_trace,
    disable_tracing,
    enable_tracing,
    interval,
    marker,
    module_of,
    span,
    tracer,
    tracing_enabled,
)

__all__ = [
    "CATALOG",
    "MetricsRegistry",
    "registry",
    "set_registry",
    "reset_registry",
    "inc",
    "set_gauge",
    "observe",
    "SpanTracer",
    "span",
    "interval",
    "marker",
    "module_of",
    "tracer",
    "enable_tracing",
    "disable_tracing",
    "tracing_enabled",
    "device_trace",
    "install_event_metrics",
]
