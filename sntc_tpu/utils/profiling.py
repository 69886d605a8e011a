"""Profiling hooks — the SparkListener/Web-UI timeline analog.

Behavioral spec: SURVEY.md §5.1: Spark's per-stage timelines come from the
listener bus; the TPU-native equivalents are (a) ``jax.profiler`` traces
viewable in TensorBoard/Perfetto (XLA op-level — far deeper than Spark's
stage view: ``sntc_tpu.obs.device_trace``), (b) the span tracer
(``sntc_tpu.obs.span``) for the stage timeline, on its own ring and
inside (a), and (c) the transfer ledger below, whose counters also
mirror into the ``sntc_tpu.obs`` metrics registry (``sntc_transfer_*``
series).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional


class TransferLedger:
    """Host↔device transfer accounting for the fused serving path.

    The whole-pipeline fusion compiler (``sntc_tpu.fuse``) exists to
    collapse per-stage host round trips into one program; this ledger is
    the EVIDENCE — every fused-segment dispatch records how many host
    arrays it uploaded and how many device outputs its finalize
    materialized.  Counts are per-DISPATCH (one fused program call):
    the per-MICRO-BATCH evidence the bench journals divides the upload/
    download deltas by the ENGINE's committed batch count, so a pipeline
    broken into N segments honestly reports N uploads per batch instead
    of hiding behind a per-dispatch ratio that is ~1 by construction.
    Thread-safe: the pipelined engine dispatches on the engine thread
    and finalizes on the delivery thread.

    **Attachment (r13):** the process-global instance
    (:func:`transfer_ledger`) used to be the ONLY ledger, which
    conflated every engine's counts — two tenant streams on one device
    were indistinguishable.  Engines now construct their OWN ledger and
    scope it around dispatch (:func:`ledger_scope`); the fused segment
    captures :func:`active_ledgers` at dispatch time and records into
    all of them, so the closure attributes correctly even though its
    finalize may run on the delivery thread.  The global stays the
    default process-wide view.

    ``tenant`` names the engine's tenant: the ledger then also mirrors
    into the ``sntc_transfer_*{tenant=...}`` metrics series.  The
    global ledger mirrors into the unlabeled series; anonymous
    per-engine ledgers (``tenant=None``) keep their own counts but do
    not mirror — the unlabeled series stays exactly the global view.
    """

    def __init__(self, tenant: Optional[str] = None, *,
                 _mirror_unlabeled: bool = False):
        self._lock = threading.Lock()
        self.tenant = tenant
        if tenant is not None:
            self._mirror_labels: Optional[Dict[str, str]] = {
                "tenant": tenant
            }
        elif _mirror_unlabeled:
            self._mirror_labels = {}
        else:
            self._mirror_labels = None
        self.dispatches = 0
        self.uploads = 0
        self.downloads = 0
        self.upload_bytes = 0
        self.download_bytes = 0

    def _mirror(self, uploads=0, upload_bytes=0, downloads=0,
                download_bytes=0, dispatches=0) -> None:
        labels = self._mirror_labels
        if labels is None:
            return
        from sntc_tpu.obs.metrics import inc

        if dispatches:
            inc("sntc_transfer_dispatches_total", dispatches, **labels)
        if uploads:
            inc("sntc_transfer_uploads_total", uploads, **labels)
        if upload_bytes:
            inc("sntc_transfer_upload_bytes_total", upload_bytes,
                **labels)
        if downloads:
            inc("sntc_transfer_downloads_total", downloads, **labels)
        if download_bytes:
            inc("sntc_transfer_download_bytes_total", download_bytes,
                **labels)

    def record_uploads(self, count: int, nbytes: int = 0) -> None:
        with self._lock:
            self.dispatches += 1
            self.uploads += int(count)
            self.upload_bytes += int(nbytes)
        self._mirror(uploads=int(count), upload_bytes=int(nbytes),
                     dispatches=1)

    def record_downloads(self, count: int, nbytes: int = 0) -> None:
        with self._lock:
            self.downloads += int(count)
            self.download_bytes += int(nbytes)
        self._mirror(downloads=int(count), download_bytes=int(nbytes))

    def record_movement(self, uploads: int = 0, upload_bytes: int = 0,
                        downloads: int = 0, download_bytes: int = 0) -> None:
        """Substrate-level host↔device movement OUTSIDE a fused dispatch
        (collective shard placement, mesh-resize re-placement, OOM
        row-split re-uploads — r22): arrays and bytes are counted but NOT
        a dispatch, so the ``dispatches`` series keeps meaning "fused
        program calls" and per-dispatch ratios stay honest."""
        with self._lock:
            self.uploads += int(uploads)
            self.upload_bytes += int(upload_bytes)
            self.downloads += int(downloads)
            self.download_bytes += int(download_bytes)
        self._mirror(uploads=int(uploads), upload_bytes=int(upload_bytes),
                     downloads=int(downloads),
                     download_bytes=int(download_bytes))

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {
                "dispatches": self.dispatches,
                "uploads": self.uploads,
                "downloads": self.downloads,
                "upload_bytes": self.upload_bytes,
                "download_bytes": self.download_bytes,
            }

    def reset(self) -> None:
        with self._lock:
            self.dispatches = self.uploads = self.downloads = 0
            self.upload_bytes = self.download_bytes = 0


# process-global instance: the default process-wide view every fused
# dispatch records into; bench/tests diff snapshots around a measured
# window (see sntc_tpu.fuse.planner).  Scoped per-engine ledgers record
# ALONGSIDE it, never instead of it.
_TRANSFER_LEDGER = TransferLedger(_mirror_unlabeled=True)

# per-thread stack of additionally-scoped ledgers.  Thread-local (not a
# contextvar) on purpose: the scope is pushed on the ENGINE thread
# around dispatch, and the fused segment snapshots active_ledgers()
# into its finalize closure — cross-thread finalize needs no
# propagation because attribution is captured at dispatch time.
_scoped = threading.local()


def transfer_ledger() -> TransferLedger:
    return _TRANSFER_LEDGER


@contextlib.contextmanager
def ledger_scope(ledger: TransferLedger):
    """Attribute fused-segment transfers dispatched inside the block to
    ``ledger`` (in addition to the process-global view)."""
    stack = getattr(_scoped, "stack", None)
    if stack is None:
        stack = _scoped.stack = []
    stack.append(ledger)
    try:
        yield ledger
    finally:
        stack.pop()


def active_ledgers() -> tuple:
    """The ledgers a dispatch happening NOW should record into: the
    process-global one plus any :func:`ledger_scope` stack on this
    thread.  Callers snapshot this at dispatch time and carry it into
    their finalize closures (see ``fuse.planner``)."""
    stack = getattr(_scoped, "stack", None)
    if not stack:
        return (_TRANSFER_LEDGER,)
    return (_TRANSFER_LEDGER, *stack)
