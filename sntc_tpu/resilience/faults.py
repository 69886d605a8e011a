"""Deterministic fault injection — named sites, armable by tests or env.

Every external-world boundary in the framework calls
``fault_point("<site>")`` before doing its real work.  Unarmed, that is
a dictionary miss — effectively free.  Armed (programmatically via
:func:`arm` or through the ``SNTC_FAULTS`` env knob), the point raises a
typed :class:`InjectedFault` on a deterministic schedule, so every retry
/ quarantine / fallback path in the codebase is exercisable in tier-1
CPU tests without real hardware failures.

Wired sites:

======================  =====================================================
``stream.wal``          ``StreamingQuery`` before the intent WAL write
``stream.read``         ``StreamingQuery`` micro-batch source read
``stream.commit``       ``StreamingQuery`` after sink delivery, before commit
``sink.write``          ``StreamingQuery`` sink delivery (per batch)
``source.parse``        byte-level parse boundaries (CSV/pcap/netflow) —
                        a :func:`fault_data` site taking the DATA kinds
``ckpt.save``           ``mlio.save_model`` (before the atomic publish)
``ckpt.load``           ``mlio.load_model`` (before manifest verification)
``probe.init``          ``resilience.device.probe_device`` recovery probe
``collective.dispatch`` ``parallel.collectives`` aggregate dispatch
``cv.fit``              ``CrossValidator`` per-(fold, grid-point) fit
``model.publish``       ``lifecycle.ModelPromoter`` before the candidate
                        checkpoint publish
``model.swap``          ``lifecycle`` promotion: post-publish/pre-swap
                        (first call) and post-swap (second call)
``flow.emit``           ``flow.FlowCaptureSource`` after window state
                        mutated, before the emitted batch is returned
``flow.evict``          ``flow.FlowFeatureEngine`` eviction pass, before
                        completed windows leave the keyed state
``flow.state_snapshot`` ``flow.FlowStateStore`` before a state snapshot
                        reaches disk
``ctl.apply``           ``serve.ServeController`` inside every live knob
                        setter, after the decision cleared the guardrails
                        and before the knob actually moves
``storage.wal``         physical WAL writes (append-log lines, files-mode
                        intent/commit json, compaction checkpoints) — a
                        :func:`fault_disk` site taking the IO kinds
``storage.journal``     every JSONL journal append (shed / controller /
                        promotion / dead-letter / repair journals)
``storage.dead_letter`` dead-letter evidence dumps (poison-batch CSVs,
                        row-level reject journals)
``storage.marker``      atomic marker/status writes (drain marker, health
                        dumps, model marker, metrics snapshots)
``storage.state``       flow-state snapshot blob writes (the physical
                        side of ``flow.state_snapshot``)
``predict.compile``     ``BatchPredictor`` before a FRESH row shape's
                        dispatch (the predict-program compile) — takes
                        the DEVICE kinds
``fuse.compile``        ``fuse.FusedSegment`` before a fresh input
                        signature compiles its fused XLA program
``device.dispatch``     ``BatchPredictor`` before every device dispatch
``kernel.compile``      ``kernels.registry`` before a FRESH
                        (kernel, signature) compiles its Pallas kernel —
                        a ``compile_error`` here poisons exactly that
                        kernel signature onto the XLA twin path
``fleet.lease``         ``serve.fleet`` worker lease renewal, before the
                        heartbeat marker reaches the coordinator root
``fleet.assign``        ``serve.fleet`` coordinator assignment publish
                        (epoch marker + assignment journal append)
``fleet.migrate``       ``serve.fleet`` tenant migration mid-ship, after
                        the source drain and before the sealed manifest
                        lands at the destination
``ingress.recv``        ``serve.ingress`` listener receive boundary —
                        also a :func:`fault_data` site taking the DATA
                        kinds (corrupt/truncated datagrams)
``ingress.spool``       ``serve.ingress`` capture-file seal, before the
                        atomic publish — a :func:`fault_disk` site
                        taking the IO kinds
``mesh.resize``         ``parallel.collectives`` elastic mesh resize,
                        after a ``device_lost`` classified and before
                        the data axis shrinks onto the survivors
======================  =====================================================

Env grammar (comma-separated specs)::

    SNTC_FAULTS=site[:kind[:prob[:seed]]][,site2:...]

``kind`` is ``exc`` (RuntimeError), ``io`` (OSError), ``timeout``
(TimeoutError), ``kill`` (``os._exit`` — the chaos-harness process
crash), a DATA kind — ``corrupt_bytes``/``truncate``/``ragged`` —
which mutates the payload at a :func:`fault_data` site instead of
raising, or a DEVICE kind —
``device_oom``/``compile_error``/``device_lost`` — which raises an
:class:`InjectedDeviceFault` whose message replicates the matching
XlaRuntimeError shape; ``prob`` in [0, 1] is evaluated per call with a
generator seeded by ``seed`` — the same env string yields the same
fault sequence in every run.  Example: arm the sink to fail ~30% of
writes deterministically::

    SNTC_FAULTS=sink.write:io:0.3:7

Programmatic arming adds Nth-call precision: ``arm("sink.write",
after=2, times=1)`` raises on exactly the 3rd call.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np

from sntc_tpu.resilience.policy import emit_event


class InjectedFault(RuntimeError):
    """Base class of every injected fault (never raised by real code)."""


class InjectedIOFault(InjectedFault, OSError):
    pass


class InjectedTimeoutFault(InjectedFault, TimeoutError):
    pass


class InjectedDiskFault(InjectedIOFault):
    """An injected *disk* failure (r17): an OSError whose ``errno`` is
    the real ENOSPC/EIO code, so ``except OSError`` handlers and
    errno-keyed failure policies treat it exactly like the genuine
    article."""

    def __init__(self, errno_code: int, msg: str):
        super().__init__(errno_code, msg)
        self.errno = errno_code


class InjectedDeviceFault(InjectedFault):
    """An injected *device/XLA-runtime* failure (r18): the message
    mimics the real ``XlaRuntimeError`` status shapes
    (``RESOURCE_EXHAUSTED: Out of memory ...``, ``INTERNAL: during
    XLA compilation ...``, ``UNAVAILABLE: device lost ...``) so
    :func:`sntc_tpu.resilience.device.classify_device_error` treats
    injected and genuine device faults identically — the compute-plane
    response ladder is exercisable without real hardware."""

    def __init__(self, msg: str, kind: str):
        super().__init__(msg)
        self.device_kind = kind


_KINDS = {
    "exc": InjectedFault,
    "io": InjectedIOFault,
    "timeout": InjectedTimeoutFault,
}

# ``kill`` is the chaos-harness kind: instead of raising, the armed
# site hard-exits the process (``os._exit``, skipping every handler and
# atexit hook — a real crash, not an exception) so crash-consistency
# tests can kill a forked engine at an exact protocol boundary.
KILL_KIND = "kill"
KILL_EXIT_CODE = 137

# Data-corruption kinds: instead of raising, an armed DATA kind mutates
# the bytes flowing through a :func:`fault_data` site (``source.parse``)
# on the same deterministic schedule — the corrupt-input chaos analog
# of ``kill``.  ``corrupt_bytes`` overwrites a few bytes with seeded
# garbage, ``truncate`` drops a seeded-length tail (a partial write /
# torn capture), ``ragged`` splices an extra delimited field into one
# line (the classic ragged-CSV row).  A data kind armed at a plain
# ``fault_point`` site is inert, and vice versa.
DATA_KINDS = ("corrupt_bytes", "truncate", "ragged")

# IO/disk kinds (r17): the storage survival plane's fault vocabulary.
# ``enospc`` and ``io_error`` raise :class:`InjectedDiskFault` — an
# OSError carrying the real errno (ENOSPC / EIO) — at any armed
# :func:`fault_point` OR :func:`fault_disk` site, modeling a full or
# failing disk at a durable write boundary.  ``torn_write`` only fires
# at :func:`fault_disk` sites (the storage plane's physical write
# helpers): the helper writes a seeded PREFIX of the payload, flushes
# it, and then raises — exactly what a crash mid-``write(2)`` leaves
# behind, so torn-tail repair paths are exercisable without a real
# kill.  ``torn_write`` armed at a plain ``fault_point`` is inert.
IO_KINDS = ("enospc", "io_error", "torn_write")

# DEVICE kinds (r18): the compute-plane fault domain's vocabulary.
# Each raises :class:`InjectedDeviceFault` whose MESSAGE replicates the
# XlaRuntimeError status shape the real backend produces (so the
# classifier in ``resilience/device.py`` cannot tell them apart):
# ``device_oom`` = RESOURCE_EXHAUSTED allocation failure, the per-batch
# OOM the dispatch splitter responds to; ``compile_error`` = a failed
# XLA compilation, the per-signature poisoning trigger;
# ``device_lost`` = the backend disappeared mid-run (chip reset,
# preemption), the HOST_DEGRADED trigger.  Armable at the compute
# sites ``predict.compile`` / ``fuse.compile`` / ``device.dispatch``.
DEVICE_KINDS = ("device_oom", "compile_error", "device_lost")

#: every kind the SNTC_FAULTS grammar accepts (docs/RESILIENCE.md keeps
#: a matching marker-delimited table; scripts/check_fault_sites.py
#: fails tier-1 when the two drift)
ALL_KINDS = (
    tuple(sorted(_KINDS)) + (KILL_KIND,) + DATA_KINDS + IO_KINDS
    + DEVICE_KINDS
)

# the documented wired sites (arming others is allowed — custom call
# sites can declare their own — but a typo'd WIRED site should be loud)
SITES = (
    "stream.wal",
    "stream.read",
    "stream.commit",
    "sink.write",
    "source.parse",
    "ckpt.save",
    "ckpt.load",
    "probe.init",
    "collective.dispatch",
    "cv.fit",
    "model.publish",
    "model.swap",
    "flow.emit",
    "flow.evict",
    "flow.state_snapshot",
    "ctl.apply",
    # durable-storage survival plane (r17): the PHYSICAL write
    # boundaries behind the logical protocol sites above — one
    # fault_disk site per durable artifact class, so an ENOSPC sweep
    # can hit every byte that reaches disk (docs/RESILIENCE.md
    # "Durable storage lifecycle" maps artifact -> site -> policy)
    "storage.wal",
    "storage.journal",
    "storage.dead_letter",
    "storage.marker",
    "storage.state",
    # compute-plane fault domain (r18): the DEVICE boundaries —
    # ``predict.compile`` fires on a FRESH dispatched row shape (the
    # predict program compile), ``fuse.compile`` on a fresh FusedSegment
    # input signature (the fused XLA program compile), and
    # ``device.dispatch`` on every device dispatch.  DEVICE kinds armed
    # here raise realistic XlaRuntimeError shapes; the response ladder
    # (OOM split / signature poison / HOST_DEGRADED) lives in
    # ``resilience/device.py`` — see docs/RESILIENCE.md "Compute-plane
    # fault domain".
    "predict.compile",
    "fuse.compile",
    "device.dispatch",
    # serving-kernel forge (r21): ``kernel.compile`` fires before a
    # FRESH (kernel, signature) compiles its hand-written Pallas kernel
    # (host-level or inside a fused trace).  A ``compile_error`` armed
    # here exercises the kernel poison ladder: exactly that kernel
    # signature falls back to its lowered-jnp twin on the XLA path —
    # never a tenant strike, never a quarantine.  See
    # docs/RESILIENCE.md "Kernel forge".
    "kernel.compile",
    # elastic serve fleet (r19): the COORDINATION boundaries of the
    # multi-process serve plane — ``fleet.lease`` before a worker's
    # lease/heartbeat marker is renewed, ``fleet.assign`` before the
    # coordinator publishes an assignment epoch, ``fleet.migrate``
    # mid-ship of a tenant's state tree (after the source drain,
    # before the sealed manifest lands).  A ``kill`` armed here is the
    # worker-crash / torn-migration chaos scenario; see
    # docs/RESILIENCE.md "Elastic serve fleet".
    "fleet.lease",
    "fleet.assign",
    "fleet.migrate",
    # live network front door (r20): the socket-ingress boundaries —
    # ``ingress.recv`` at the listener receive path (DATA kinds corrupt
    # the datagram/frame exactly like ``source.parse``; a ``kill`` here
    # crashes mid-receive, before anything reached the spool) and
    # ``ingress.spool`` at the capture-file seal (IO kinds model a
    # full/failing spool disk — the artifact's SHED policy counts the
    # loss instead of dying; a ``kill`` is the kill-mid-spool chaos
    # scenario).  See docs/RESILIENCE.md "Network ingress".
    "ingress.recv",
    "ingress.spool",
    # mesh substrate (r22): ``mesh.resize`` fires inside the collective
    # layer's elastic response, after a ``device_lost`` classified but
    # before the data axis shrinks and the batch re-places on the
    # survivors — arming it exercises a resize that itself dies (the
    # double-fault path falls through to the caller / host domain).
    # See docs/RESILIENCE.md "Mesh substrate".
    "mesh.resize",
    # warm-standby disaster recovery (r23): the REPLICATION boundaries
    # of the standby plane — ``repl.ship`` before each changed artifact
    # file is copied into the replica tree, ``repl.apply`` before the
    # sealed replica manifest publishes (the point where the ship
    # becomes visible), ``repl.barrier`` before a commit-barrier record
    # is appended to the replicated barrier log.  A ``kill`` armed here
    # is the torn-ship / torn-barrier chaos scenario: the replica must
    # converge bitwise on restart and a half-shipped file must
    # quarantine, never promote.  IO kinds degrade (counted, journaled)
    # — replication failures never fail the serving engine.  See
    # docs/RESILIENCE.md "Disaster recovery".
    "repl.ship",
    "repl.apply",
    "repl.barrier",
)


@dataclass
class _Armed:
    site: str
    kind: str = "exc"
    prob: float = 1.0
    seed: int = 0
    after: int = 0  # calls to let through before fault logic starts
    times: Optional[int] = None  # max faults to raise; None = unlimited
    from_env: bool = False
    calls: int = 0
    raised: int = 0
    rng: np.random.Generator = field(default=None)  # type: ignore[assignment]

    def __post_init__(self):
        if self.kind not in ALL_KINDS:
            raise ValueError(
                f"unknown fault kind {self.kind!r}; expected one of "
                f"{list(ALL_KINDS)}"
            )
        if not 0.0 <= self.prob <= 1.0:
            raise ValueError(f"fault prob must lie in [0, 1], got {self.prob}")
        self.rng = np.random.default_rng(self.seed)

    def decide(self) -> bool:
        """Called under the registry lock, once per fault_point hit."""
        self.calls += 1
        if self.calls <= self.after:
            return False
        if self.times is not None and self.raised >= self.times:
            return False
        # consume one deterministic draw per eligible call, so the
        # fault sequence depends only on (seed, call index)
        fire = (
            self.prob >= 1.0 or float(self.rng.uniform()) < self.prob
        )
        if fire:
            self.raised += 1
        return fire


_registry: Dict[str, _Armed] = {}
_lock = threading.Lock()
_env_installed: Optional[str] = None


def arm(
    site: str,
    kind: str = "exc",
    prob: float = 1.0,
    seed: int = 0,
    *,
    after: int = 0,
    times: Optional[int] = 1,
    _from_env: bool = False,
) -> None:
    """Arm ``site``; default raises on the next call, exactly once."""
    spec = _Armed(
        site=site, kind=kind, prob=prob, seed=seed, after=after,
        times=times, from_env=_from_env,
    )
    with _lock:
        _registry[site] = spec


def disarm(site: str) -> None:
    with _lock:
        _registry.pop(site, None)


def clear() -> None:
    """Drop every armed fault (programmatic AND env-installed; the env
    string is re-installed on the next fault_point if still set)."""
    global _env_installed
    with _lock:
        _registry.clear()
        _env_installed = None


def call_count(site: str) -> int:
    with _lock:
        spec = _registry.get(site)
        return spec.calls if spec else 0


def parse_faults_env(raw: str) -> list:
    """Parse the ``SNTC_FAULTS`` grammar into arm() argument dicts.

    Every grammar failure raises a ``ValueError`` that NAMES the
    offending comma-separated segment and says which field broke —
    wrong arity, empty site, unknown kind, non-numeric or out-of-range
    prob, non-integer seed — never a bare unpack/conversion error."""
    out = []
    for chunk in raw.split(","):
        chunk = chunk.strip()
        if not chunk:
            continue
        parts = chunk.split(":")
        if len(parts) > 4:
            raise ValueError(
                f"malformed SNTC_FAULTS spec {chunk!r}: expected at most "
                f"4 ':'-separated fields (site[:kind[:prob[:seed]]]), "
                f"got {len(parts)}"
            )
        if not parts[0]:
            raise ValueError(
                f"malformed SNTC_FAULTS spec {chunk!r}: empty site name"
            )
        spec = {"site": parts[0]}
        if len(parts) > 1:
            if parts[1] not in ALL_KINDS:
                raise ValueError(
                    f"malformed SNTC_FAULTS spec {chunk!r}: unknown kind "
                    f"{parts[1]!r}; expected one of {list(ALL_KINDS)}"
                )
            spec["kind"] = parts[1]
        if len(parts) > 2:
            try:
                prob = float(parts[2])
            except ValueError:
                raise ValueError(
                    f"malformed SNTC_FAULTS spec {chunk!r}: prob "
                    f"{parts[2]!r} is not a float"
                ) from None
            if not 0.0 <= prob <= 1.0:
                raise ValueError(
                    f"malformed SNTC_FAULTS spec {chunk!r}: prob {prob} "
                    "must lie in [0, 1]"
                )
            spec["prob"] = prob
        if len(parts) > 3:
            try:
                spec["seed"] = int(parts[3])
            except ValueError:
                raise ValueError(
                    f"malformed SNTC_FAULTS spec {chunk!r}: seed "
                    f"{parts[3]!r} is not an int"
                ) from None
        out.append(spec)
    return out


def _sync_env() -> None:
    """(Re)install env-armed faults when SNTC_FAULTS changed; never
    touches programmatically armed sites.  A malformed string warns
    ONCE on stderr and arms nothing — raising from here would surface
    inside arbitrary fault_point call sites, where the retry/quarantine
    machinery would misclassify the config typo as a real site fault."""
    global _env_installed
    raw = os.environ.get("SNTC_FAULTS") or None
    if raw == _env_installed:
        return
    with _lock:
        for site in [s for s, a in _registry.items() if a.from_env]:
            del _registry[site]
    if raw:
        import sys

        try:
            specs = parse_faults_env(raw)
            for spec in specs:
                # env faults are probabilistic and unlimited — the knob
                # models an unreliable environment, not a one-shot test
                arm(times=None, _from_env=True, **spec)
        except ValueError as e:
            with _lock:
                for site in [
                    s for s, a in _registry.items() if a.from_env
                ]:
                    del _registry[site]
            print(
                f"sntc_tpu: ignoring malformed SNTC_FAULTS: {e}",
                file=sys.stderr,
            )
    _env_installed = raw


def _count_injection(site: str, kind: str) -> None:
    """Mirror one fired injection into the metrics plane (obs) — chaos
    evidence next to the production counters it perturbs.  Never fatal:
    the injection itself is the point, not its accounting."""
    try:
        from sntc_tpu.obs.metrics import inc

        inc("sntc_faults_injected_total", site=site, kind=kind)
    except Exception:
        pass


def fault_point(site: str, tenant: Optional[str] = None) -> None:
    """The per-site hook real code calls; raises when armed + scheduled.
    A spec armed with a DATA kind is inert here — byte corruption only
    makes sense where bytes flow (:func:`fault_data`).

    ``tenant`` (r12) checks the tenant-NAMESPACED site first —
    ``tenant/<id>/<site>`` — then falls back to the bare site, so
    multi-tenant chaos can arm one tenant's boundary
    (``SNTC_FAULTS=tenant/a/stream.wal:kill``) without touching its
    neighbors, while a bare-site fault still hits every tenant (the
    shared-environment failure mode)."""
    _sync_env()
    spec = None
    if tenant is not None:
        spec = _registry.get(f"tenant/{tenant}/{site}")
    if spec is None:
        spec = _registry.get(site)
    if spec is None or spec.kind in DATA_KINDS or spec.kind == "torn_write":
        return
    site = spec.site  # event/error name the ARMED site (namespaced)
    with _lock:
        fire = spec.decide()
        call = spec.calls
    if fire:
        _count_injection(site, spec.kind)
        emit_event(
            event="fault_injected", site=site, kind=spec.kind, call=call
        )
        if spec.kind == KILL_KIND:
            # hard crash, not an exception: no finally blocks, no WAL
            # flushes, no atexit — what a SIGKILL/OOM/preemption does
            os._exit(KILL_EXIT_CODE)
        if spec.kind in ("enospc", "io_error"):
            raise _disk_fault(spec.kind, site, call)
        if spec.kind in DEVICE_KINDS:
            raise _device_fault(spec.kind, site, call)
        raise _KINDS[spec.kind](
            f"injected {spec.kind} fault at site {site!r} (call {call})"
        )


def _disk_fault(kind: str, site: str, call: int) -> "InjectedDiskFault":
    import errno as _errno

    code = _errno.ENOSPC if kind == "enospc" else _errno.EIO
    return InjectedDiskFault(
        code,
        f"injected {kind} fault at site {site!r} (call {call})",
    )


def _device_fault(kind: str, site: str, call: int) -> "InjectedDeviceFault":
    """The message replicates the real XlaRuntimeError status line for
    the kind, so ``classify_device_error`` exercises the SAME pattern
    match genuine backend failures would hit."""
    if kind == "device_oom":
        msg = (
            "RESOURCE_EXHAUSTED: Out of memory while trying to "
            "allocate 1073741824 bytes. "
            f"[injected device_oom at site {site!r} (call {call})]"
        )
    elif kind == "compile_error":
        msg = (
            "INTERNAL: during XLA compilation: injected compile_error "
            f"at site {site!r} (call {call})"
        )
    else:  # device_lost
        msg = (
            "UNAVAILABLE: device lost: backend restarted "
            f"[injected device_lost at site {site!r} (call {call})]"
        )
    return InjectedDeviceFault(msg, kind)


def fault_disk(site: str, tenant: Optional[str] = None) -> Optional[float]:
    """The physical-write hook the storage plane's helpers call before
    bytes reach disk (``storage.*`` sites).  Unarmed — or armed with a
    non-IO kind — it returns None.  Armed with ``enospc``/``io_error``
    it raises :class:`InjectedDiskFault` (nothing was written, the
    full-disk shape).  Armed with ``torn_write`` it returns a seeded
    fraction in (0, 1): the CALLER writes that prefix of its payload,
    flushes it, and raises — so the injected failure leaves exactly the
    torn tail a crash mid-``write(2)`` would, for the repair paths to
    find.  Same tenant-namespaced lookup as :func:`fault_point`."""
    _sync_env()
    spec = None
    if tenant is not None:
        spec = _registry.get(f"tenant/{tenant}/{site}")
    if spec is None:
        spec = _registry.get(site)
    if spec is None or spec.kind not in IO_KINDS:
        return None
    site = spec.site
    with _lock:
        fire = spec.decide()
        call = spec.calls
        torn = float(spec.rng.uniform(0.2, 0.8)) if fire else 0.0
    if not fire:
        return None
    _count_injection(site, spec.kind)
    emit_event(
        event="fault_injected", site=site, kind=spec.kind, call=call
    )
    if spec.kind == "torn_write":
        return torn
    raise _disk_fault(spec.kind, site, call)


def _mutate(kind: str, data: bytes, draws: "np.ndarray") -> bytes:
    """Apply one deterministic corruption to ``data``.  ``draws`` is a
    flat vector of uniform [0, 1) floats consumed positionally, so the
    mutation depends only on (seed, call index, payload length)."""
    n = len(data)
    if n == 0:
        return data
    if kind == "truncate":
        # keep a strict prefix: the torn-write / partial-capture shape
        return data[: int(draws[0] * n)]
    if kind == "corrupt_bytes":
        buf = bytearray(data)
        k = max(1, n // 64)
        for i in range(k):
            pos = int(draws[2 * i] * n)
            buf[pos] = int(draws[2 * i + 1] * 256) % 256
        return bytes(buf)
    # ragged: splice an extra delimited field into one line.  Pick a
    # DATA line when the payload is line-structured (never the header);
    # otherwise splice at a raw offset — for binary payloads this is
    # mid-stream junk, the framing analog of a ragged row.
    lines = data.split(b"\n")
    if len(lines) > 2:
        li = 1 + int(draws[0] * max(1, len(lines) - 2))
        lines[li] = lines[li] + b",__sntc_ragged__"
        return b"\n".join(lines)
    pos = int(draws[0] * n)
    return data[:pos] + b",__sntc_ragged__," + data[pos:]


def data_fault_armed(site: str) -> bool:
    """True when a DATA kind is armed at ``site`` — callers that would
    have to buffer a whole payload just to route it through
    :func:`fault_data` (e.g. a CSV reader that otherwise streams from
    the path) check this first and skip the buffering when unarmed."""
    _sync_env()
    spec = _registry.get(site)
    return spec is not None and spec.kind in DATA_KINDS


def fault_data(site: str, data: bytes) -> bytes:
    """The byte-corruption hook parse boundaries call on their raw
    input (``source.parse``).  Unarmed — or armed with a non-DATA
    kind — it returns ``data`` untouched; armed with ``corrupt_bytes``
    / ``truncate`` / ``ragged`` it deterministically mutates the
    payload.

    Unlike :func:`fault_point`, the fire decision and the mutation
    randomness derive from ``(seed, payload bytes)`` — NOT from the
    shared call-order rng — because parse sites run on reader/prefetch
    threads whose interleaving varies run to run: the same corpus under
    the same ``SNTC_FAULTS`` string corrupts the same payloads the same
    way regardless of reader concurrency.  ``after``/``times`` are
    still honored (bookkept under the registry lock), but which
    payloads they gate depends on arrival order — use ``prob`` for
    reproducible multi-threaded chaos."""
    import zlib

    _sync_env()
    spec = _registry.get(site)
    if spec is None or spec.kind not in DATA_KINDS:
        return data
    with _lock:
        spec.calls += 1
        call = spec.calls
        if call <= spec.after or (
            spec.times is not None and spec.raised >= spec.times
        ):
            return data
    rng = np.random.default_rng(
        [spec.seed, zlib.crc32(data), len(data)]
    )
    if not (spec.prob >= 1.0 or float(rng.uniform()) < spec.prob):
        return data
    with _lock:
        spec.raised += 1
    draws = rng.uniform(size=2 * max(1, len(data) // 64))
    mutated = _mutate(spec.kind, data, draws)
    _count_injection(site, spec.kind)
    emit_event(
        event="fault_injected", site=site, kind=spec.kind, call=call,
        bytes_in=len(data), bytes_out=len(mutated),
    )
    return mutated
