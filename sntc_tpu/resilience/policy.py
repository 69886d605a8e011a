"""Retry policies — the Spark task-retry (``spark.task.maxFailures``)
analog for a single-process JAX pipeline.

Behavioral spec: Spark's execution layer retries failed tasks with
backoff and keeps the job alive (MLlib rode on it for free); tf.data
treats input-pipeline fault handling as a first-class concern.  Here the
substrate is one process talking to flaky externals — a source that
times out, a sink volume that hiccups, a checkpoint torn mid-write — so
the unit of retry is a *site*: a named callable boundary
(``stream.read``, ``sink.write``, ``ckpt.load``, ...).

:class:`RetryPolicy` is a frozen value object: max attempts, exponential
backoff with DETERMINISTIC seeded jitter (the schedule is a pure
function of the policy — tests assert it exactly), an optional overall
deadline, and a retryable-exception classifier.
:func:`with_retries` executes a thunk under a policy and emits
structured JSONL events (``retry`` / ``retry_success`` /
``retry_exhausted``) through :mod:`sntc_tpu.utils.logging` — set
``SNTC_RESILIENCE_LOG=<path>`` to persist them; the last 512 events are
always inspectable in-process via :func:`recent_events`.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple, Type

import numpy as np

# top-level on purpose: the ring-eviction mirror below runs under
# _events_lock, and a lazy import THERE could re-enter this module's
# machinery mid-import; obs.metrics imports only the stdlib
from sntc_tpu.obs.metrics import inc as _metrics_inc
from sntc_tpu.utils.logging import MetricsLogger


class RetryExhausted(RuntimeError):
    """Every attempt a policy allowed has failed; wraps the last error."""

    def __init__(self, site: str, attempts: int, last: BaseException):
        super().__init__(
            f"{site}: {attempts} attempt(s) failed; last error: {last!r}"
        )
        self.site = site
        self.attempts = attempts
        self.last_exception = last


@dataclass(frozen=True)
class RetryPolicy:
    """Immutable retry spec; the backoff schedule is deterministic.

    ``jitter`` is a ± fraction applied to each exponential delay with a
    ``numpy`` generator seeded by ``seed`` — the same policy always
    yields the same schedule, so sleep sequences are assertable in
    tests and reproducible in incident logs.  ``deadline_s`` bounds the
    TOTAL elapsed time: a backoff sleep that would overshoot it is
    CLAMPED to the remaining budget (the final attempt still runs at
    the deadline), and once the deadline has elapsed no further attempt
    is made.
    """

    max_attempts: int = 3
    base_delay_s: float = 0.05
    multiplier: float = 2.0
    max_delay_s: float = 5.0
    jitter: float = 0.1
    seed: int = 0
    deadline_s: Optional[float] = None
    retryable: Tuple[Type[BaseException], ...] = (Exception,)

    def __post_init__(self):
        if self.max_attempts < 1:
            raise ValueError("max_attempts must be >= 1")
        if self.base_delay_s < 0 or self.max_delay_s < 0:
            raise ValueError("delays must be >= 0")
        if not 0.0 <= self.jitter <= 1.0:
            raise ValueError("jitter must lie in [0, 1]")

    def is_retryable(self, exc: BaseException) -> bool:
        return isinstance(exc, self.retryable)

    def backoff_schedule(self) -> List[float]:
        """Delay before retry i (i = 1 .. max_attempts-1), exactly."""
        rng = np.random.default_rng(self.seed)
        out = []
        for i in range(max(0, self.max_attempts - 1)):
            base = min(
                self.base_delay_s * self.multiplier**i, self.max_delay_s
            )
            u = float(rng.uniform(-1.0, 1.0))
            out.append(max(0.0, base * (1.0 + self.jitter * u)))
        return out


def int_from_env(var: str, default: int, minimum: int = 0) -> int:
    """Shared env-int parser for retry knobs
    (``SNTC_COLLECTIVE_RETRIES``, ...): malformed values warn once on
    stderr and fall back — a config typo must never crash startup."""
    raw = os.environ.get(var)
    if raw is None:
        return default
    try:
        val = int(raw)
    except (TypeError, ValueError):
        print(
            f"sntc_tpu: malformed {var}={raw!r}; using {default}",
            file=sys.stderr,
        )
        return default
    return max(minimum, val)


# ---------------------------------------------------------------------------
# structured events: JSONL through MetricsLogger + an in-process ring
# ---------------------------------------------------------------------------

_RECENT_MAX = 512
_recent: "deque[Dict[str, Any]]" = deque(maxlen=_RECENT_MAX)
_events_lock = threading.Lock()
_events_dropped = 0
# per-tenant eviction breakdown (r12): records carrying a ``tenant``
# field count against their tenant when the ring evicts them, so a
# flooding tenant's event pressure is attributable — the fair-share
# evidence the serve daemon journals.  Untagged records count under
# the int total only (single-tenant emit paths stay unchanged).
_events_dropped_by_tenant: Dict[str, int] = {}
_logger: Optional[MetricsLogger] = None
_observers: List[Callable[[Dict[str, Any]], None]] = []


def _events_logger() -> MetricsLogger:
    # pathless: the MetricsLogger only shapes records (step/elapsed);
    # file persistence is handled below in APPEND mode — the run-logger's
    # truncate-on-construction would clobber a log shared with parent or
    # sibling processes (bench --isolate children, probe subprocesses)
    global _logger
    if _logger is None:
        _logger = MetricsLogger(None)
    return _logger


def emit_event(**fields: Any) -> Dict[str, Any]:
    """Append one structured resilience event (JSONL when
    ``SNTC_RESILIENCE_LOG`` is set; always kept in the in-process ring).

    The ring is hard-capped at ``_RECENT_MAX`` records — a long-running
    query emits events for the life of the process, and the cap turns
    that into bounded memory.  Evictions are counted
    (:func:`events_dropped`), never silent.  Thread-safe: the engine
    loop, the watchdog thread, and ``--health-json`` snapshots all
    touch the ring concurrently.
    """
    global _events_dropped
    path = os.environ.get("SNTC_RESILIENCE_LOG")
    with _events_lock:
        # logger init, the step counter, file append, and the ring all
        # mutate under the ONE lock — the engine loop and the watchdog
        # thread emit concurrently, and a torn step sequence would break
        # the step-watermark windows bench journaling relies on
        record = _events_logger().log(**fields)
        # wall AND monotonic timestamps on EVERY event record: replay
        # analysis across tenants (or processes) orders by ``ts``;
        # intra-process interval math uses ``mono``, which never jumps
        # with the system clock.  Emitter-supplied values win.
        if "ts" not in record:
            record["ts"] = time.time()
        if "mono" not in record:
            record["mono"] = time.monotonic()
        if path:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with open(path, "a") as f:  # storage: unbounded(opt-in debug event log)
                f.write(json.dumps(record) + "\n")
        if len(_recent) == _recent.maxlen:
            _events_dropped += 1
            evicted_tenant = _recent[0].get("tenant")
            if evicted_tenant is not None:
                _events_dropped_by_tenant[evicted_tenant] = (
                    _events_dropped_by_tenant.get(evicted_tenant, 0) + 1
                )
            try:  # mirror into the metrics plane (obs), never fatally
                _metrics_inc(
                    "sntc_events_dropped_total",
                    **(
                        {"tenant": evicted_tenant}
                        if evicted_tenant is not None else {}
                    ),
                )
            except Exception:
                pass
        _recent.append(record)
        observers = list(_observers)
    # observers run OUTSIDE the ring lock: an observer that emits (a
    # health change triggered by this event) must not deadlock.  A
    # RAISING observer is evicted, not propagated — emit_event runs
    # inside retry loops and breaker transitions, and an exception here
    # would replace the real error the resilience machinery is handling
    for fn in observers:
        try:
            fn(record)
        except Exception as e:
            remove_event_observer(fn)
            print(
                f"sntc_tpu: event observer {fn!r} raised {e!r}; "
                "observer removed",
                file=sys.stderr,
            )
    return record


def recent_events(
    site: Optional[str] = None, event: Optional[str] = None
) -> List[Dict[str, Any]]:
    """The in-process event ring, optionally filtered by site/event."""
    with _events_lock:
        snapshot = list(_recent)
    return [
        r
        for r in snapshot
        if (site is None or r.get("site") == site)
        and (event is None or r.get("event") == event)
    ]


def events_dropped(by_tenant: bool = False):
    """Events evicted from the ring since the last :func:`clear_events`
    — nonzero means ``recent_events`` is a suffix, not the full story.
    ``by_tenant=True`` returns the per-tenant breakdown instead (a
    dict of tenant → evictions, only tenant-tagged records counted) —
    the serve daemon's noisy-neighbor evidence."""
    with _events_lock:
        if by_tenant:
            return dict(_events_dropped_by_tenant)
        return _events_dropped


def add_event_observer(fn: Callable[[Dict[str, Any]], None]) -> None:
    """Register ``fn(record)`` to run on every future event (the
    :class:`~sntc_tpu.resilience.health.HealthMonitor` feed)."""
    with _events_lock:
        if fn not in _observers:
            _observers.append(fn)


def remove_event_observer(fn: Callable[[Dict[str, Any]], None]) -> None:
    with _events_lock:
        if fn in _observers:
            _observers.remove(fn)


def event_observer_count() -> int:
    """Registered observers right now — the leak regression's probe: a
    component that attaches an observer must detach it on teardown, so
    the count stays flat across component lifecycles."""
    with _events_lock:
        return len(_observers)


def clear_events() -> None:
    global _events_dropped
    with _events_lock:
        _recent.clear()
        _events_dropped = 0
        _events_dropped_by_tenant.clear()


# ---------------------------------------------------------------------------
# the executor
# ---------------------------------------------------------------------------


def with_retries(
    fn: Callable[[], Any],
    policy: Optional[RetryPolicy] = None,
    *,
    site: str = "unspecified",
    sleep: Callable[[float], None] = time.sleep,
    clock: Callable[[], float] = time.monotonic,
) -> Any:
    """Run ``fn()`` under ``policy``; emit structured events per retry.

    Non-retryable exceptions propagate unchanged.  Retryable failures
    sleep the policy's deterministic backoff and re-invoke; when
    attempts (or the deadline) run out, :class:`RetryExhausted` wraps
    the last error.  The deadline clamps, not truncates: a backoff that
    would overshoot ``deadline_s`` is shortened to exactly the
    remaining budget and the final attempt still runs — the executor
    never sleeps past the deadline just to raise
    :class:`RetryExhausted` late, and never gives up with budget left.
    ``sleep`` and ``clock`` are injectable so tests assert schedules
    and deadline behavior without wall-clock cost.
    """
    policy = policy or RetryPolicy()
    schedule = policy.backoff_schedule()
    t0 = clock()
    for attempt in range(1, policy.max_attempts + 1):
        try:
            out = fn()
        except BaseException as e:
            if not policy.is_retryable(e):
                raise
            delay = schedule[attempt - 1] if attempt <= len(schedule) else 0.0
            elapsed = clock() - t0
            remaining = (
                None if policy.deadline_s is None
                else policy.deadline_s - elapsed
            )
            out_of_time = remaining is not None and remaining <= 0
            if attempt >= policy.max_attempts or out_of_time:
                emit_event(
                    event="retry_exhausted", site=site, attempts=attempt,
                    error=repr(e), deadline_hit=bool(out_of_time),
                )
                raise RetryExhausted(site, attempt, e) from e
            if remaining is not None:
                delay = min(delay, remaining)
            emit_event(
                event="retry", site=site, attempt=attempt,
                delay_s=round(delay, 6), error=repr(e),
            )
            sleep(delay)
        else:
            if attempt > 1:
                emit_event(
                    event="retry_success", site=site, attempts=attempt
                )
            return out
    raise AssertionError("unreachable")
