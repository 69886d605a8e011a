"""Compute-plane fault domain (r18): classify device/XLA runtime
errors and respond per kind instead of blind-retrying the same doomed
program.

Every other layer of the framework has a declared survival story —
storage (r17), ingest (r10/r15), serving (r12/r16), lifecycle (r11) —
but until now a device OOM, a wedged or failed compile, or a lost
backend surfaced as a generic ``predict.dispatch`` retry that re-ran
the exact same program against the exact same dead device.  This
module is the missing fault domain:

* :func:`classify_device_error` maps any exception chain onto the
  DEVICE kind vocabulary (``device_oom`` / ``compile_error`` /
  ``device_lost``) by the same message patterns the real
  ``XlaRuntimeError`` status lines carry — injected faults
  (:class:`~sntc_tpu.resilience.faults.InjectedDeviceFault`) and
  genuine backend failures classify identically.

* :class:`DeviceFaultDomain` holds the response state machine:

  - **device_oom** → the dispatcher splits the micro-batch in half
    (recursively, floored at the shape-bucket minimum) and steps the
    bucket floor down, journaling a ``device_oom_split`` decision —
    retry ON device with a smaller program, not the same one.
  - **compile_error** (or a compile exceeding the per-signature
    wall-time watchdog, ``compile_budget_s``) → exactly that
    (segment, signature) is POISONED in the plan cache and served
    through the eager host fallback forever after; other signatures
    keep compiling on device.
  - **device_lost**, or ``degrade_after`` consecutive device-attributed
    failures → the whole predictor flips **HOST_DEGRADED**: every
    dispatch takes the host path, the model component reports DEGRADED,
    the ``sntc_device_state`` gauge flips to 1, and a probe-gated
    recovery tick dispatches a tiny program (:func:`probe_device`) OFF
    the hot path until the device answers again — then serving returns
    to the device with the compile ledger intact (no churn on
    re-entry).

  Device-attributed errors are PLATFORM faults: the serving engine
  routes them here instead of into the per-batch poison machinery, so
  they never quarantine a batch prematurely and never strike a tenant's
  escalation ladder (docs/RESILIENCE.md "Compute-plane fault domain").
"""

from __future__ import annotations

import re
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

from sntc_tpu.resilience.policy import emit_event

DEVICE_OK = "DEVICE_OK"
HOST_DEGRADED = "HOST_DEGRADED"

_OOM_RE = re.compile(
    r"RESOURCE_EXHAUSTED|out of memory|OOM when allocating"
    r"|failed to allocate.*(?:memory|bytes)",
    re.IGNORECASE,
)
_COMPILE_RE = re.compile(
    r"XLA compilation|during compile|compilation fail|failed to compile"
    r"|compile_error",
    re.IGNORECASE,
)
_LOST_RE = re.compile(
    r"device (?:lost|halted|removed|reset)|UNAVAILABLE"
    r"|FAILED_PRECONDITION|backend (?:restart|lost|unavailable)"
    r"|heartbeat|device_lost",
    re.IGNORECASE,
)


def _xla_shaped(exc: BaseException) -> bool:
    """Only XLA-runtime-shaped errors may classify: the injected device
    fault, jaxlib's ``XlaRuntimeError`` (matched by type name — jaxlib
    moves the class between releases), or an error another layer
    already tagged with ``device_kind``.  A ``ValueError("cannot
    compile regex")`` from user code must never flip serving onto the
    host path."""
    if getattr(exc, "device_kind", None) is not None:
        return True
    for klass in type(exc).__mro__:
        if klass.__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
            return True
    return False


def classify_device_error(exc: Optional[BaseException]) -> Optional[str]:
    """The DEVICE kind an exception chain carries, or None for
    anything that is not a device/XLA runtime failure.  Walks
    ``__cause__``/``__context__`` (bounded) so a wrapped finalize error
    still classifies; patterns are checked OOM → compile → lost so a
    ``RESOURCE_EXHAUSTED`` raised during compilation responds as the
    OOM it is."""
    seen = 0
    while exc is not None and seen < 8:
        kind = getattr(exc, "device_kind", None)
        if kind is not None:
            return kind
        if _xla_shaped(exc):
            msg = str(exc)
            if _OOM_RE.search(msg):
                return "device_oom"
            if _COMPILE_RE.search(msg):
                return "compile_error"
            if _LOST_RE.search(msg):
                return "device_lost"
        exc = exc.__cause__ or exc.__context__
        seen += 1
    return None


class DeviceExecError(RuntimeError):
    """A device-attributed dispatch/finalize failure with its execution
    context threaded through (the r17 file+offset discipline applied to
    the compute plane): which batch, which fused segment, which input
    signature — so an error surfacing on the overlap-sink delivery
    thread still names the work that died, not just the symptom.
    ``device_kind`` makes it classify without re-matching patterns."""

    def __init__(
        self,
        message: str,
        *,
        kind: Optional[str] = None,
        batch_id: Optional[int] = None,
        segment: Optional[int] = None,
        signature: Optional[str] = None,
    ):
        super().__init__(message)
        self.device_kind = kind
        self.batch_id = batch_id
        self.segment = segment
        self.signature = signature


def annotate_batch(exc: BaseException, batch_id: int) -> BaseException:
    """Thread the batch id through an in-flight error chain without
    changing its type (retry/breaker/quarantine handlers keep working):
    a ``__notes__`` entry where the runtime supports it, and a
    ``batch_id`` attribute either way."""
    if getattr(exc, "batch_id", None) is None:
        try:
            exc.batch_id = batch_id
        except Exception:
            pass
        note = f"[sntc] while finalizing/delivering batch {batch_id}"
        add_note = getattr(exc, "add_note", None)
        if add_note is not None:
            try:
                add_note(note)
            except Exception:
                pass
    return exc


@dataclass
class DevicePolicy:
    """Response-ladder tuning for one :class:`DeviceFaultDomain`.

    ``oom_split_depth`` bounds the recursive micro-batch halvings one
    dispatch may attempt; ``bucket_floor_min`` is where the OOM
    responder stops stepping the predictor's shape-bucket floor down;
    ``compile_budget_s`` arms the per-signature compile wall-time
    watchdog (None/0 = unarmed); ``degrade_after`` consecutive
    device-attributed failures (any kind) flip HOST_DEGRADED even
    without a ``device_lost``; ``probe_interval_s`` paces the
    recovery probe while degraded."""

    oom_split_depth: int = 4
    bucket_floor_min: int = 1
    #: clean dispatches after the last OOM before a stepped-down
    #: bucket floor is restored to its cold value — the step-down is
    #: an emergency response to transient memory pressure, not a
    #: permanent ratchet (a tiny floor forever = fresh compiles for
    #: every small batch size, the churn the buckets exist to prevent)
    floor_restore_after: int = 64
    compile_budget_s: Optional[float] = None
    degrade_after: int = 3
    probe_interval_s: float = 30.0
    journal_keep: int = 256

    def __post_init__(self):
        if self.compile_budget_s is not None and self.compile_budget_s <= 0:
            self.compile_budget_s = None
        self.oom_split_depth = max(1, int(self.oom_split_depth))
        self.bucket_floor_min = max(1, int(self.bucket_floor_min))
        self.degrade_after = max(1, int(self.degrade_after))


def _metrics():
    from sntc_tpu.obs import metrics

    return metrics


def probe_device() -> bool:
    """The recovery probe: one tiny dispatch on the default device, in
    THIS process.  A chip belongs to one process at a time — the
    degraded server still holds it, so a child ``python -c "import jax;
    jax.devices()"`` could never succeed on a local chip; the only
    process that can ask the device anything is this one.  Called from
    the domain's background thread, so a dispatch that hangs never
    wedges serving; any error is a still-dead verdict (the caller
    catches).  ``probe.init`` is the fault-injection site."""
    import jax
    import jax.numpy as jnp

    from sntc_tpu.resilience.faults import fault_point

    fault_point("probe.init")
    jax.block_until_ready(jnp.zeros((8, 128), jnp.float32) + 1.0)
    return True


class DeviceFaultDomain:
    """The compute-plane survival state machine (module docstring).

    One domain models ONE device: the ServeDaemon shares a single
    domain across every tenant's predictor, exactly as the tenants
    share the physical device — a platform fault degrades the plane
    once, not once per tenant.  Thread-safe: predictors dispatch from
    engine AND delivery threads.

    ``probe_fn`` (default: :func:`probe_device`, an in-process
    dispatch) decides recovery; with ``probe_async=True`` (the default)
    it runs on a background daemon thread so a hung dispatch can never
    stall the serving loop — the verdict is applied at the next
    :meth:`tick`.  Tests inject a synchronous ``probe_fn`` and a fake
    clock for deterministic arcs."""

    def __init__(
        self,
        policy: Optional[DevicePolicy] = None,
        *,
        probe_fn: Optional[Callable[[], bool]] = None,
        clock: Callable[[], float] = time.monotonic,
        probe_async: bool = True,
    ):
        self.policy = policy or DevicePolicy()
        self._probe_fn = probe_fn
        self._clock = clock
        self._probe_async = bool(probe_async)
        self._lock = threading.Lock()
        self._state = DEVICE_OK
        self._degraded_reason: Optional[str] = None
        self._degraded_at: Optional[float] = None
        self._consecutive = 0
        self._last_probe: Optional[float] = None
        self._probe_inflight = False
        self._probe_verdict: Optional[bool] = None
        # evidence
        self.faults: Dict[str, int] = {}
        self.oom_splits = 0
        self.bucket_floor_steps = 0
        self.poisoned_signatures = 0
        self.fallback_batches = 0
        self.recoveries = 0
        self.degradations = 0
        self.probes = 0
        self.last_recovery_latency_s: Optional[float] = None
        self.journal: List[dict] = []
        self._gauge(0)

    # -- state --------------------------------------------------------------

    @property
    def state(self) -> str:
        return self._state

    @property
    def host_degraded(self) -> bool:
        return self._state == HOST_DEGRADED

    def _gauge(self, value: int) -> None:
        try:
            _metrics().set_gauge("sntc_device_state", value)
        except Exception:
            pass

    def _journal(self, record: dict) -> None:
        record = dict(record, ts=time.time())
        with self._lock:
            self.journal.append(record)
            if len(self.journal) > self.policy.journal_keep:
                del self.journal[: -self.policy.journal_keep]

    # -- fault intake --------------------------------------------------------

    def note_fault(self, kind: str, *, site: str, **context: Any) -> None:
        """One device-attributed failure: count it, emit the
        ``device_fault`` event (never a strike event), and escalate to
        HOST_DEGRADED on ``device_lost`` or on the ``degrade_after``-th
        consecutive failure of any kind."""
        with self._lock:
            self.faults[kind] = self.faults.get(kind, 0) + 1
            self._consecutive += 1
            consecutive = self._consecutive
        try:
            _metrics().inc("sntc_device_faults_total", kind=kind, site=site)
        except Exception:
            pass
        emit_event(
            event="device_fault", component="model", site=site,
            kind=kind, consecutive=consecutive, **context,
        )
        if kind == "device_lost" or consecutive >= self.policy.degrade_after:
            self.enter_host_degraded(
                f"{kind} at {site}"
                if kind == "device_lost"
                else f"{consecutive} consecutive device faults "
                f"(last: {kind} at {site})"
            )

    def fault_count(self) -> int:
        """Total device faults noted so far — the dispatcher snapshots
        this around a dispatch so a fault ABSORBED inside it (a fused
        segment poisoning its signature) is not immediately cancelled
        by the enclosing dispatch's success."""
        with self._lock:
            return sum(self.faults.values())

    def note_success(self) -> None:
        """A clean device dispatch: the consecutive-failure streak
        resets (the degradation trigger is *sustained* failure)."""
        if self._consecutive:
            with self._lock:
                self._consecutive = 0

    def note_oom_split(self, *, rows: int, depth: int,
                       bucket_floor: int) -> None:
        with self._lock:
            self.oom_splits += 1
        try:
            _metrics().inc("sntc_device_oom_splits_total")
        except Exception:
            pass
        self._journal({
            "decision": "device_oom_split", "rows": rows,
            "depth": depth, "bucket_floor": bucket_floor,
        })
        emit_event(
            event="device_oom_split", component="model",
            site="device.dispatch", rows=rows, depth=depth,
        )

    def note_mesh_resize(self, *, old: int, new: int, axis: str,
                         site: str) -> None:
        """A mesh participant dropped out and the collective layer
        RESIZED (r22): the data axis shrank ``old`` → ``new`` and the
        fit continues on the survivors.  Journaled as a first-class
        decision — it is the elastic alternative to
        :meth:`enter_host_degraded`, so it must leave the same kind of
        evidence trail.  Counts as a device fault for the metrics/event
        plane but does NOT feed the consecutive-failure streak: the
        resize already IS the response."""
        with self._lock:
            self.faults["device_lost"] = (
                self.faults.get("device_lost", 0) + 1
            )
        try:
            _metrics().inc(
                "sntc_device_faults_total", kind="device_lost", site=site
            )
        except Exception:
            pass
        self._journal({
            "decision": "mesh_resize", "axis": axis,
            "from": old, "to": new, "site": site,
        })
        emit_event(
            event="mesh_resize", component="model", site=site,
            axis=axis, old=old, new=new,
        )

    def note_bucket_floor(self, old: int, new: int) -> None:
        with self._lock:
            self.bucket_floor_steps += 1
        self._journal({
            "decision": "bucket_floor_down", "from": old, "to": new,
        })

    def note_bucket_restore(self, old: int, new: int) -> None:
        self._journal({
            "decision": "bucket_floor_restored", "from": old, "to": new,
        })

    def note_unpoisoned(self, count: int) -> None:
        """Poisons cleared (a hot-swap discarded the programs they
        belonged to): keep the live poisoned-signatures gauge honest —
        it reports pairs CURRENTLY serving the host fallback, not a
        lifetime total."""
        if count <= 0:
            return
        with self._lock:
            self.poisoned_signatures = max(
                0, self.poisoned_signatures - count
            )
            current = self.poisoned_signatures
        try:
            _metrics().set_gauge(
                "sntc_device_poisoned_signatures", current
            )
        except Exception:
            pass
        self._journal({"decision": "poisons_cleared", "count": count})

    def note_poisoned(self, *, site: str, signature: str,
                      reason: str, segment: Optional[int] = None) -> None:
        """One (segment, signature) left the device path for good —
        compile failure or watchdog breach."""
        with self._lock:
            self.poisoned_signatures += 1
            count = self.poisoned_signatures
        try:
            m = _metrics()
            m.set_gauge("sntc_device_poisoned_signatures", count)
        except Exception:
            pass
        self._journal({
            "decision": "signature_poisoned", "site": site,
            "segment": segment, "signature": signature, "reason": reason,
        })
        emit_event(
            event="signature_poisoned", component="model", site=site,
            segment=segment, signature=signature, reason=reason,
        )

    def note_fallback(self, poisoned: bool = False) -> None:
        """One batch served through the eager host path (poisoned
        signature or HOST_DEGRADED)."""
        with self._lock:
            self.fallback_batches += 1
        try:
            _metrics().inc("sntc_device_fallback_batches_total")
        except Exception:
            pass

    # -- the HOST_DEGRADED state machine -------------------------------------

    def enter_host_degraded(self, reason: str) -> None:
        with self._lock:
            if self._state == HOST_DEGRADED:
                return
            self._state = HOST_DEGRADED
            self._degraded_reason = reason
            self._degraded_at = self._clock()
            self._last_probe = None
            self._probe_verdict = None
            self.degradations += 1
        self._gauge(1)
        self._journal({"decision": "host_degraded", "reason": reason})
        emit_event(
            event="device_degraded", component="model", reason=reason,
        )

    def _run_probe(self) -> None:
        probe = self._probe_fn or probe_device
        try:
            verdict = bool(probe())
        except Exception:
            verdict = False
        with self._lock:
            self._probe_verdict = verdict
            self._probe_inflight = False
            self.probes += 1

    def tick(self) -> None:
        """The recovery tick, called once per engine round (cheap when
        DEVICE_OK).  While degraded: apply a finished probe's verdict
        (recover on success), and launch the next probe once
        ``probe_interval_s`` has passed — on a background thread by
        default, so a probe dispatch that HANGS never wedges
        serving."""
        if self._state != HOST_DEGRADED:
            return
        with self._lock:
            verdict, self._probe_verdict = self._probe_verdict, None
            inflight = self._probe_inflight
            last = self._last_probe
        if verdict:
            self._recover()
            return
        now = self._clock()
        if inflight or (
            last is not None and now - last < self.policy.probe_interval_s
        ):
            return
        with self._lock:
            self._last_probe = now
            self._probe_inflight = True
        if self._probe_async:
            threading.Thread(
                target=self._run_probe, name="sntc-device-probe",
                daemon=True,
            ).start()
        else:
            self._run_probe()
            with self._lock:
                verdict, self._probe_verdict = self._probe_verdict, None
            if verdict:
                self._recover()

    def _recover(self) -> None:
        with self._lock:
            if self._state != HOST_DEGRADED:
                return
            self._state = DEVICE_OK
            self._consecutive = 0
            latency = (
                self._clock() - self._degraded_at
                if self._degraded_at is not None else None
            )
            self.last_recovery_latency_s = latency
            self._degraded_reason = None
            self._degraded_at = None
            self.recoveries += 1
        self._gauge(0)
        try:
            _metrics().inc("sntc_device_recoveries_total")
        except Exception:
            pass
        self._journal({
            "decision": "device_recovered",
            "recovery_latency_s": latency,
        })
        emit_event(
            event="device_recovered", component="model",
            recovery_latency_s=latency,
        )

    # -- evidence -----------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "state": self._state,
                "degraded_reason": self._degraded_reason,
                "consecutive_faults": self._consecutive,
                "faults": dict(self.faults),
                "oom_splits": self.oom_splits,
                "bucket_floor_steps": self.bucket_floor_steps,
                "poisoned_signatures": self.poisoned_signatures,
                "fallback_batches": self.fallback_batches,
                "degradations": self.degradations,
                "recoveries": self.recoveries,
                "probes": self.probes,
                "recovery_latency_s": self.last_recovery_latency_s,
                "journal": list(self.journal[-8:]),
            }
