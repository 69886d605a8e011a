"""Process-wide metrics registry: counters, gauges, fixed-bucket
histograms, labels, Prometheus/JSONL exposition.

Design constraints, in priority order:

1. **Hot-path writes are cheap** — one dict lookup + one small lock per
   increment.  The streaming engine calls :func:`inc` per micro-batch
   (not per row), so the registry never shows up in a profile; bench
   config 5 pins the whole substrate's overhead at ≤ 5% rows/s
   (docs/OBSERVABILITY.md has the measured numbers).
2. **Snapshots never block writers** — :meth:`MetricsRegistry.snapshot`
   reads live series values without taking the write locks (CPython
   makes each individual read atomic); a snapshot taken mid-increment
   may be one tick stale on one series, never torn across the registry.
3. **Bounded cardinality** — every metric holds at most
   ``max_label_sets`` distinct label sets; beyond the cap, writes to
   any further label set collapse into a reserved ``overflow="true"``
   series and each such write is counted (:meth:`label_overflows`),
   never silent.  A misbehaving label (a batch id, a file path)
   degrades the one metric, not the process.
4. **Deterministic in tests** — wall/monotonic clocks are injectable
   per registry, so JSONL exposition records are assertable exactly.

Every metric this codebase emits is declared in :data:`CATALOG` (name →
type/help/labels/buckets) — the single source of truth that
``docs/OBSERVABILITY.md`` documents and ``scripts/check_metric_names
.py`` drift-checks against the code in tier-1.  Undeclared names are
rejected: an unregistered metric is exactly the ad-hoc-ledger drift
this package exists to end.
"""

from __future__ import annotations

import json
import os
import threading
import time
from bisect import bisect_left
from typing import Any, Dict, List, Optional, Tuple

COUNTER = "counter"
GAUGE = "gauge"
HISTOGRAM = "histogram"

# seconds; covers sub-ms device dispatches through multi-second batches
LATENCY_BUCKETS = (
    0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
    10.0, 30.0,
)

#: THE metric catalog: every name the codebase may emit, with its type,
#: allowed labels, and help text.  ``scripts/check_metric_names.py``
#: pins code ⇔ CATALOG ⇔ docs/OBSERVABILITY.md in tier-1.
CATALOG: Dict[str, Dict[str, Any]] = {
    # -- the structured event stream (obs.bridge) -------------------------
    "sntc_events_total": dict(
        type=COUNTER, labels=("event", "site", "tenant"),
        help="Structured resilience/lifecycle events by name, site, "
        "and tenant (the _emit/emit_event stream, consolidated).",
    ),
    "sntc_events_dropped_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Event-ring evictions (legacy view: events_dropped()).",
    ),
    "sntc_rows_rejected_total": dict(
        type=COUNTER, labels=("reason", "tenant"),
        help="Rows excised by data-plane admission, by reason code.",
    ),
    "sntc_shed_offsets_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Source offsets dropped by load shedding (shed journal).",
    ),
    "sntc_batches_quarantined_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Poison batches journaled to the dead-letter sink.",
    ),
    "sntc_faults_injected_total": dict(
        type=COUNTER, labels=("site", "kind"),
        help="Deterministic fault injections fired (SNTC_FAULTS).",
    ),
    # -- the serving engine -----------------------------------------------
    "sntc_batches_committed_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Micro-batches committed to the WAL (incl. quarantined).",
    ),
    "sntc_rows_committed_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Input rows across committed micro-batches.",
    ),
    "sntc_batch_duration_seconds": dict(
        type=HISTOGRAM, labels=("tenant",), buckets=LATENCY_BUCKETS,
        help="WAL-intent→commit latency per micro-batch (the "
        "recentProgress durationMs distribution).",
    ),
    "sntc_source_prefetch_hits_total": dict(
        type=COUNTER, labels=(),
        help="get_batch calls served from a staged prefetch read.",
    ),
    "sntc_source_prefetch_misses_total": dict(
        type=COUNTER, labels=(),
        help="get_batch calls that fell through to a synchronous read "
        "while prefetch was armed.",
    ),
    # -- ingest -------------------------------------------------------------
    "sntc_ingest_files_parsed_total": dict(
        type=COUNTER, labels=(),
        help="Source files parsed by load_csv.",
    ),
    "sntc_ingest_rows_parsed_total": dict(
        type=COUNTER, labels=(),
        help="Rows parsed out of source files by load_csv.",
    ),
    "sntc_ingest_bytes_read_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Raw source bytes read by ingest (CSV parse, capture "
        "decode).",
    ),
    # -- the ingest source graph + autotuner (data/pipeline, data/autotune) --
    "sntc_ingest_stage_seconds": dict(
        type=HISTOGRAM, labels=("stage", "tenant"),
        buckets=LATENCY_BUCKETS,
        help="Per-item latency of each ingest source-graph stage "
        "(read/parse/admit/bucket/stage) — the autotuner's feedback "
        "signal.",
    ),
    "sntc_ingest_queue_depth": dict(
        type=GAUGE, labels=("stage", "tenant"),
        help="Current occupancy of a source-graph stage queue (the "
        "prefetch staging queue).",
    ),
    "sntc_ingest_autotune_decisions_total": dict(
        type=COUNTER, labels=("knob", "direction", "tenant"),
        help="Applied ingest-autotuner knob changes, by knob and "
        "direction.",
    ),
    "sntc_ingest_knob_value": dict(
        type=GAUGE, labels=("knob", "tenant"),
        help="Current value of each autotuned ingest knob "
        "(read_workers / prefetch_batches / pipeline_depth).",
    ),
    # -- live network ingress (serve/ingress, r20) --------------------------
    "sntc_ingress_datagrams_total": dict(
        type=COUNTER, labels=("tenant",),
        help="UDP datagrams accepted at the ingress receive boundary "
        "(pre-spool; the conservation law's 'received' side).",
    ),
    "sntc_ingress_frames_total": dict(
        type=COUNTER, labels=("tenant",),
        help="TCP length-prefixed frames accepted at the ingress "
        "receive boundary.",
    ),
    "sntc_ingress_bytes_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Payload bytes accepted at the ingress receive boundary.",
    ),
    "sntc_ingress_dropped_total": dict(
        type=COUNTER, labels=("reason", "tenant"),
        help="Ingress payloads shed, by reason (ring_overflow / "
        "spool_over_budget / spool_error / torn_frame / oversize_frame "
        "/ recv_error / close_discard) — counted shed, never silent "
        "loss: received == spooled + dropped after a drain.",
    ),
    "sntc_ingress_sealed_files_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Capture files sealed (fsynced atomic rename) into the "
        "ingress spool.",
    ),
    "sntc_ingress_pruned_files_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Committed capture files pruned by spool retention "
        "(keep-N / disk budget).",
    ),
    "sntc_ingress_spool_bytes": dict(
        type=GAUGE, labels=("tenant",),
        help="Live bytes in the ingress spool directory.",
    ),
    "sntc_ingress_ring_depth": dict(
        type=GAUGE, labels=("tenant",),
        help="Payloads waiting in the bounded ingress ring.",
    ),
    "sntc_ingress_backpressure_state": dict(
        type=GAUGE, labels=("tenant",),
        help="1 while TCP ingress is pausing reads (spool over "
        "budget), 0 otherwise.",
    ),
    "sntc_ingress_connections": dict(
        type=GAUGE, labels=("tenant",),
        help="Live TCP ingress connections.",
    ),
    # -- predict / compile ledgers ------------------------------------------
    "sntc_predict_compile_events_total": dict(
        type=COUNTER, labels=(),
        help="Distinct dispatched row shapes across BatchPredictors "
        "(each costs at most one XLA compile; legacy view: "
        "BatchPredictor.compile_events).",
    ),
    "sntc_xla_compiles_total": dict(
        type=COUNTER, labels=("outcome",),
        help="Executables XLA built (outcome=compiled) or loaded from the "
        "persistent compilation cache (outcome=cache_loaded): one per "
        "miss of a jitted function's own cache, fit path included "
        "(utils/compile_cache.py listener on jax.monitoring).",
    ),
    "sntc_xla_compile_seconds_total": dict(
        type=COUNTER, labels=("outcome", "program"),
        help="Seconds jax reported for those builds and loads "
        "(backend_compile_duration), by outcome and by program (jax's "
        "fun_name): for outcome=cache_loaded the retrieval and "
        "deserialisation.  program is bounded by the number of distinct "
        "jitted programs of a process; past the registry's cap the rest "
        "fold into overflow=\"true\", so the sum stays right.",
    ),
    "sntc_xla_trace_seconds_total": dict(
        type=COUNTER, labels=("program",),
        help="Seconds of jaxpr tracing (jaxpr_trace_duration) by program, "
        "each span's own: an inner jit traced inside an outer one is "
        "charged to the inner, so the series add up to wall seconds.  "
        "program is bounded as on sntc_xla_compile_seconds_total.",
    ),
    "sntc_xla_lower_seconds_total": dict(
        type=COUNTER, labels=("program",),
        help="Seconds of lowering jaxpr to MLIR "
        "(jaxpr_to_mlir_module_duration; a Pallas kernel's Mosaic "
        "lowering is in here) by program, each span's own as on "
        "sntc_xla_trace_seconds_total.",
    ),
    "sntc_pipeline_first_fit_seconds": dict(
        type=GAUGE, labels=(),
        help="Wall seconds of the process's first Pipeline.fit (the body "
        "of its pipeline.fit span), set once when it returns: the fit "
        "that pays every program's trace, lowering and compile or load "
        "(core/base.py).",
    ),
    "sntc_process_device_ready_seconds": dict(
        type=GAUGE, labels=(),
        help="Seconds from the process's start (the kernel's, not the "
        "package's import) to its first mesh, set once: the interpreter, "
        "the imports and the runtime reaching its devices "
        "(parallel/mesh.py).",
    ),
    "sntc_predict_bucket_hits_total": dict(
        type=COUNTER, labels=(),
        help="Dispatches that reused an already-seen row shape.",
    ),
    "sntc_predict_padded_rows_total": dict(
        type=COUNTER, labels=(),
        help="Wasted rows shape-bucket padding cost.",
    ),
    "sntc_predict_head_dispatch_total": dict(
        type=COUNTER, labels=("path",),
        help="Classifier-head predicts by where they ran: path=device "
        "(a jitted program, fused into a segment or the head's own) or "
        "path=host (the numpy predict at or below "
        "SNTC_SERVE_HOST_ROWS, and every eager fallback of a head "
        "that has one).  chip_smoke.py asserts every served batch "
        "took path=device.",
    ),
    "sntc_fuse_compile_events_total": dict(
        type=COUNTER, labels=(),
        help="Distinct input signatures compiled across FusedSegments.",
    ),
    "sntc_fuse_fallbacks_total": dict(
        type=COUNTER, labels=(),
        help="FusedSegment eager fallbacks (empty frame / dtype gate).",
    ),
    # -- host↔device transfers (utils.profiling.TransferLedger mirror) ------
    "sntc_transfer_dispatches_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Fused-program dispatches (unlabeled series = the "
        "process-global TransferLedger; tenant series = the "
        "per-engine ledgers).",
    ),
    "sntc_transfer_uploads_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Host→device array uploads by fused dispatches.",
    ),
    "sntc_transfer_downloads_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Device→host output materializations by fused finalizes.",
    ),
    "sntc_transfer_upload_bytes_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Bytes uploaded host→device by fused dispatches.",
    ),
    "sntc_transfer_download_bytes_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Bytes materialized device→host by fused finalizes.",
    ),
    "sntc_transfer_pad_bytes_total": dict(
        type=COUNTER, labels=("where",),
        help="Bytes touched to pad a row-sharded placement to its shard "
        "multiple (parallel/collectives.py:_cached_shard_put): "
        "where=host counts the whole host array each time it is copied "
        "on the host for the pad's sake (arrays under 1 MiB, and meshes "
        "that span processes), where=device the shard padded on its own "
        "chip (fit-scale arrays: no host copy).",
    ),
    # -- host copies of a whole vector column on the fit path (feature/) ----
    "sntc_feature_copy_bytes_total": dict(
        type=COUNTER, labels=("site", "layout"),
        help="Bytes a feature stage wrote to materialise a whole vector "
        "column on the host: site=assemble.stack (the assembler's "
        "float32 matrix), chi2.extract (only when the selector has to "
        "cast its input), select.take (feature.selection.take_columns; "
        "layout=base_rows | columns | generic is the copy it chose from "
        "the input's strides).",
    ),
    "sntc_feature_pooled_copies_total": dict(
        type=COUNTER, labels=("site",),
        help="Whole-column host copies a pool of workers made "
        "(feature/stack.py:stack_rows, at and over POOL_MIN_BYTES of "
        "result): site=assemble.stack | select.take; a serving "
        "micro-batch adds 0.",
    ),
    "sntc_feature_index_passes_total": dict(
        type=COUNTER, labels=("site",),
        help="Hash passes a StringIndexer made over a column "
        "(feature/string_indexer.py): site=fit (a bare fit), transform "
        "(a fitted model indexing a frame), fit_transform (a pipeline "
        "fit: the vocabulary and the index column from one pass).  A "
        "fit of the benchmark's cells adds fit_transform 1 and nothing "
        "else.",
    ),
    # -- boosting rounds on the fit path (models/tree/gbt*.py) ---------------
    "sntc_boost_rounds_total": dict(
        type=COUNTER, labels=("estimator",),
        help="Boosting rounds completed, by loop: estimator=gbt_classifier "
        "(the binary GBTClassifier), gbt_ovr (the one-vs-rest loop, K "
        "class trees a round), gbt_regressor.",
    ),
    "sntc_boost_trees_total": dict(
        type=COUNTER, labels=("estimator",),
        help="Trees grown by boosting rounds (a one-vs-rest round adds "
        "K, the binary and the regression loops 1), same labels as "
        "sntc_boost_rounds_total.",
    ),
    # -- the perceptron's optimiser on the fit path (models/mlp.py) ---------
    "sntc_mlp_grad_evals_total": dict(
        type=COUNTER, labels=(),
        help="Loss-and-gradient evaluations made by "
        "MultilayerPerceptronClassifier fits under solver=gd, one a step, "
        "added once a fit from the optimiser's returned n_iters "
        "(models/mlp.py, after the mlp.optimize span); an L-BFGS fit's "
        "line search returns no count and adds nothing.",
    ),
    # -- collective layer over the mesh substrate (parallel/mesh, r22) ------
    "sntc_collective_dispatches_total": dict(
        type=COUNTER, labels=("op", "axis"),
        help="SPMD collective dispatches over a mesh axis, by "
        "aggregate op (tree_aggregate / kmeans.lloyd / lda.e_step / "
        "pic.power / tree.histogram).",
    ),
    "sntc_collective_bytes_moved_total": dict(
        type=COUNTER, labels=("op", "axis"),
        help="Ring-allreduce wire bytes (2·(n-1)·payload) moved by "
        "collective dispatches — the SparCML baseline a compressed "
        "reduction must beat; loop-carried psums count once per "
        "dispatch (documented lower bound).",
    ),
    "sntc_collective_mesh_devices": dict(
        type=GAUGE, labels=("axis",),
        help="Live mesh shape: devices along each declared axis "
        "(shrinks on a journaled mesh_resize).",
    ),
    "sntc_collective_resizes_total": dict(
        type=COUNTER, labels=(),
        help="Elastic mesh resizes — a device_lost answered by "
        "shrinking the data axis onto the survivors instead of "
        "flipping HOST_DEGRADED.",
    ),
    # -- health / breakers / drift -------------------------------------------
    "sntc_health_state": dict(
        type=GAUGE, labels=("component",),
        help="Component health (0=OK, 1=DEGRADED, 2=UNHEALTHY).",
    ),
    "sntc_breaker_state": dict(
        type=GAUGE, labels=("site",),
        help="Circuit-breaker state (0=closed, 1=half_open, 2=open).",
    ),
    "sntc_drift_divergence": dict(
        type=GAUGE, labels=("component",),
        help="Latest Jensen-Shannon divergence the drift monitor saw.",
    ),
    # -- multi-tenant scheduler ----------------------------------------------
    "sntc_daemon_ticks_total": dict(
        type=COUNTER, labels=(),
        help="ServeDaemon scheduling rounds.",
    ),
    "sntc_tenant_state": dict(
        type=GAUGE, labels=("tenant",),
        help="Tenant ladder state (0=OK, 1=THROTTLED, 2=QUARANTINED, "
        "3=STOPPED).",
    ),
    "sntc_tenant_deficit": dict(
        type=GAUGE, labels=("tenant",),
        help="DRR scheduler deficit after the last round.",
    ),
    "sntc_tenant_strikes_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Unhealthy strikes counted against the tenant ladder.",
    ),
    # -- the stateful flow-feature engine (sntc_tpu/flow) --------------------
    "sntc_flow_records_consumed_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Parser records (packets/datagram rows) accepted into "
        "keyed window state.",
    ),
    "sntc_flow_late_records_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Records dropped behind the watermark (reason code "
        "late_record).",
    ),
    "sntc_flow_out_of_order_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Accepted records that arrived behind the stream head "
        "but inside the lateness bound.",
    ),
    "sntc_flow_windows_emitted_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Completed flow windows emitted as feature rows.",
    ),
    "sntc_flow_evictions_total": dict(
        type=COUNTER, labels=("reason", "tenant"),
        help="Flows evicted from keyed state, by reason (watermark / "
        "state_cap / flush).",
    ),
    "sntc_flow_snapshots_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Operator-state snapshots published at commit.",
    ),
    "sntc_flow_active_flows": dict(
        type=GAUGE, labels=("tenant",),
        help="Open (uncompleted) flow windows held in keyed state.",
    ),
    "sntc_flow_state_packets": dict(
        type=GAUGE, labels=("tenant",),
        help="Buffered parser records across all open windows (the "
        "watermark-bounded state size).",
    ),
    "sntc_flow_state_bytes": dict(
        type=GAUGE, labels=("tenant",),
        help="Size of the last published operator-state snapshot.",
    ),
    # -- the closed-loop SLO controller (serve/controller) -------------------
    "sntc_ctl_windows_total": dict(
        type=COUNTER, labels=(),
        help="SLO-controller observation windows closed.",
    ),
    "sntc_ctl_decisions_total": dict(
        type=COUNTER, labels=("action", "knob", "tenant"),
        help="SLO-controller decisions (applied / budget_denied / "
        "frozen / delegated / escalated), by knob and tenant.",
    ),
    "sntc_ctl_knob_value": dict(
        type=GAUGE, labels=("knob", "tenant"),
        help="Current value of each controller-steered serving knob "
        "(pipeline_depth / shape_buckets / weight / quota / shed / "
        "escalate / migrate / scale_out; ladder knobs report their "
        "ladder index).",
    ),
    "sntc_ctl_slo_compliant": dict(
        type=GAUGE, labels=("slo", "tenant"),
        help="Per-window SLO compliance verdict (1 = compliant, 0 = "
        "violating) for each declared SLO axis (p99 / throughput / "
        "shed).",
    ),
    "sntc_ctl_window_p99_seconds": dict(
        type=GAUGE, labels=("tenant",),
        help="Windowed p99 batch latency the controller computed from "
        "the sntc_batch_duration_seconds bucket deltas.",
    ),
    # -- the tracer's own accounting -----------------------------------------
    "sntc_spans_dropped_total": dict(
        type=COUNTER, labels=(),
        help="Spans evicted from the trace ring buffer.",
    ),
    # -- the durable-storage survival plane (resilience/storage, r17) --------
    "sntc_disk_bytes": dict(
        type=GAUGE, labels=("artifact", "tenant"),
        help="On-disk bytes per registered durable artifact under a "
        "checkpoint root (artifact=total is the whole tree).",
    ),
    "sntc_disk_files": dict(
        type=GAUGE, labels=("artifact", "tenant"),
        help="On-disk file count per registered durable artifact "
        "(artifact=total is the whole tree).",
    ),
    "sntc_disk_budget_bytes": dict(
        type=GAUGE, labels=("tenant",),
        help="Declared disk byte budget for a checkpoint root "
        "(global when unlabeled, per-tenant when labeled).",
    ),
    "sntc_storage_write_errors_total": dict(
        type=COUNTER, labels=("artifact", "tenant"),
        help="Failed durable writes (ENOSPC/EIO, real or injected), "
        "by artifact.",
    ),
    "sntc_storage_degraded_state": dict(
        type=GAUGE, labels=("artifact", "tenant"),
        help="1 while an artifact is in a storage_degraded episode "
        "(records buffering in memory), 0 after recovery.",
    ),
    "sntc_storage_repairs_total": dict(
        type=COUNTER, labels=("artifact", "tenant"),
        help="Automatic storage repairs (torn-tail truncations, "
        "corrupt-blob quarantines), journaled to "
        "storage_repair.jsonl.",
    ),
    "sntc_dead_letter_dropped_total": dict(
        type=COUNTER, labels=("artifact", "tenant"),
        help="Dead-letter evidence files dropped by the keep-N/"
        "size-cap retention policy.",
    ),
    "sntc_wal_compactions_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Append-WAL compactions (sealed checkpoint written, "
        "offsets/commits logs truncated).",
    ),
    # -- the compute-plane fault domain (resilience/device, r18) --------------
    "sntc_device_state": dict(
        type=GAUGE, labels=(),
        help="Device serving state of the process's fault domain "
        "(0=DEVICE_OK, 1=HOST_DEGRADED — every dispatch on the eager "
        "host fallback until the recovery probe succeeds).",
    ),
    "sntc_device_faults_total": dict(
        type=COUNTER, labels=("kind", "site"),
        help="Classified device/XLA runtime failures (device_oom / "
        "compile_error / device_lost), by fault site.",
    ),
    "sntc_device_oom_splits_total": dict(
        type=COUNTER, labels=(),
        help="Micro-batch halvings the OOM responder performed "
        "(device_oom_split decisions; retried on device at the "
        "smaller shape).",
    ),
    "sntc_device_poisoned_signatures": dict(
        type=GAUGE, labels=(),
        help="(segment, signature) pairs poisoned out of the device "
        "plan cache after a compile failure or watchdog breach — each "
        "serves through the eager host fallback.",
    ),
    "sntc_device_fallback_batches_total": dict(
        type=COUNTER, labels=(),
        help="Dispatches served through the eager host fallback "
        "(poisoned signature or HOST_DEGRADED).",
    ),
    # -- serving-kernel forge + MFU/roofline plane (r21) ----------------
    "sntc_kernel_dispatch_total": dict(
        type=COUNTER, labels=("kernel", "impl"),
        help="Hand-written kernel executions by kernel name and "
        "implementation (pallas on hardware, interpret on CPU "
        "tier-1); the registered twin paths count under "
        "sntc_kernel_fallback_total instead.",
    ),
    "sntc_kernel_fallback_total": dict(
        type=COUNTER, labels=("kernel", "reason"),
        help="Kernel-tier calls served on the lowered-jnp/numpy twin "
        "path, by reason (off / guard / poisoned / compile_error / "
        "segment / mesh — a dispatch sharded over the serve mesh: the "
        "kernel tier is single-device).",
    ),
    "sntc_kernel_tree_hist_column_tiles_total": dict(
        type=COUNTER, labels=(),
        help="128-column MXU array tiles the planned tree_hist products "
        "of the fits issue (a level's trees stacked as columns of one "
        "product; counted once a fit from the static plan, "
        "grower._level_plan). The kernel's time follows this count.",
    ),
    "sntc_kernel_tree_hist_columns_total": dict(
        type=COUNTER, labels=(),
        help="Columns among those tiles that carry a term of a statistic "
        "(3 bfloat16 terms x trees x histogrammed nodes x statistics); "
        "over 128 x sntc_kernel_tree_hist_column_tiles_total it is the "
        "array's fill.",
    ),
    "sntc_kernel_tree_hist_psum_total": dict(
        type=COUNTER, labels=(),
        help="All-reduces (psum over the mesh's row axis) the planned "
        "tree_hist histograms of the fits take: one a node-group pass a "
        "level, counted once a fit from the static plan "
        "(grower._level_plan), outside the trace; 0 on a mesh of one.",
    ),
    "sntc_kernel_tree_hist_psum_bytes_total": dict(
        type=COUNTER, labels=(),
        help="Payload bytes of those all-reduces: the float32 histograms "
        "summed over the shards ([trees, nodes, features, bins, "
        "statistics] a pass); 0 on a mesh of one.",
    ),
    "sntc_kernel_poisoned_signatures": dict(
        type=GAUGE, labels=(),
        help="(kernel, signature) pairs poisoned onto the XLA twin "
        "path after a kernel compile failure — each serves bitwise "
        "on the twin, never striking a tenant.",
    ),
    "sntc_mfu_ratio": dict(
        type=GAUGE, labels=("segment",),
        help="Achieved FLOP/s over probed peak FLOP/s per fused "
        "serving segment (XLA cost_analysis x measured dispatch "
        "time; only under SNTC_OBS_COST_ANALYSIS=1 — see "
        "obs/cost.py and the peak_source caveat).",
    ),
    "sntc_mfu_bw_ratio": dict(
        type=GAUGE, labels=("segment",),
        help="Achieved memory bandwidth over probed peak bandwidth "
        "per fused serving segment (same hook and caveats as "
        "sntc_mfu_ratio).",
    ),
    "sntc_device_recoveries_total": dict(
        type=COUNTER, labels=(),
        help="HOST_DEGRADED -> DEVICE_OK transitions (the probe-gated "
        "recovery tick restored device serving).",
    ),
    # -- the elastic serve fleet (serve/fleet, r19) ---------------------------
    "sntc_fleet_worker_state": dict(
        type=GAUGE, labels=("worker",),
        help="Coordinator's liveness verdict per worker (1 = lease "
        "current, 0 = lease expired / declared dead).",
    ),
    "sntc_fleet_leases_renewed_total": dict(
        type=COUNTER, labels=("worker",),
        help="Worker lease/heartbeat renewals observed by the "
        "coordinator.",
    ),
    "sntc_fleet_leases_expired_total": dict(
        type=COUNTER, labels=("worker",),
        help="Lease expiries — a worker missed its TTL and was "
        "declared dead; its tenants were redistributed.",
    ),
    "sntc_fleet_migrations_total": dict(
        type=COUNTER, labels=("reason", "outcome"),
        help="Tenant migrations by reason (rebalance / worker_dead / "
        "controller / join) and outcome (completed / reverted).",
    ),
    "sntc_fleet_tenants_value": dict(
        type=GAUGE, labels=("worker",),
        help="Tenants currently assigned to each worker (the "
        "coordinator's placement view).",
    ),
    "sntc_fleet_rows_value": dict(
        type=GAUGE, labels=("worker",),
        help="Rows committed as reported by each worker's last "
        "heartbeat (worker=fleet is the aggregate across live "
        "workers).",
    ),
    # -- warm-standby replication (resilience/replicate, r23) -----------------
    "sntc_repl_ships_total": dict(
        type=COUNTER, labels=("tenant", "outcome"),
        help="Replication ship passes by outcome (completed / error). "
        "An error pass degraded — it was journaled and retries at the "
        "next commit; the serving engine never notices.",
    ),
    "sntc_repl_ship_files_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Artifact files copied into the standby replica tree "
        "(changed-content files only; unchanged files are skipped by "
        "stamp/sha).",
    ),
    "sntc_repl_ship_bytes_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Bytes shipped into the standby replica tree.",
    ),
    "sntc_repl_barriers_sealed_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Commit-barrier records sealed into the replicated "
        "barrier log — each one is a provably consistent promotion "
        "point (the replica holds everything through its batch_id).",
    ),
    "sntc_repl_lag_batches": dict(
        type=GAUGE, labels=("tenant",),
        help="Committed batches not yet covered by a sealed barrier "
        "(the batch component of RPO; 0 right after each barrier).",
    ),
    "sntc_repl_lag_seconds": dict(
        type=GAUGE, labels=("tenant",),
        help="Seconds since the last sealed barrier (the time "
        "component of RPO).",
    ),
    "sntc_repl_lag_bytes": dict(
        type=GAUGE, labels=("tenant",),
        help="Estimated un-replicated primary bytes (what a primary "
        "loss right now would cost; stat-only estimate, refreshed on "
        "degraded ships and zeroed at each barrier).",
    ),
    "sntc_repl_divergence_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Replica-vs-manifest or replica-vs-primary divergences "
        "found by promotion or anti-entropy fsck (each one also "
        "journals a replica_diverged event).",
    ),
    "sntc_repl_promotions_total": dict(
        type=COUNTER, labels=("outcome",),
        help="Standby promotions by outcome (completed / failed). A "
        "failed promotion never leaves a partially promoted tree.",
    ),
    "sntc_repl_tail_loss_rows_total": dict(
        type=COUNTER, labels=("tenant",),
        help="Rows counted lost beyond the last sealed barrier at "
        "promotion (the counted_tail_loss term of the loss-accounting "
        "law: committed == replicated_through_barrier + "
        "counted_tail_loss).",
    ),
}

_OVERFLOW_KEY: Tuple[Tuple[str, str], ...] = (("overflow", "true"),)


class _Series:
    """One label set of one metric.  Counters/gauges keep ``value``;
    histograms keep per-bucket counts plus sum/count."""

    __slots__ = ("labels", "value", "bucket_counts", "sum", "count")

    def __init__(self, labels: Tuple[Tuple[str, str], ...],
                 n_buckets: int = 0):
        self.labels = labels
        self.value = 0.0
        self.bucket_counts = [0] * n_buckets if n_buckets else None
        self.sum = 0.0
        self.count = 0


class MetricsRegistry:
    """Registry of cataloged metrics (module docstring has the design).

    ``clock``/``mono`` are the wall/monotonic time sources used by the
    JSONL exposition — inject constants for deterministic test output.
    ``max_label_sets`` caps per-metric label cardinality.
    """

    def __init__(
        self,
        *,
        clock=time.time,
        mono=time.monotonic,
        max_label_sets: int = 64,
    ):
        self._clock = clock
        self._mono = mono
        self.max_label_sets = int(max_label_sets)
        self._lock = threading.Lock()  # series creation only
        # name -> (spec, {labelkey: _Series}, write lock)
        self._metrics: Dict[str, Tuple[dict, Dict, threading.Lock]] = {}
        self._label_overflows = 0
        self._jsonl_records = 0

    # -- series resolution ---------------------------------------------------

    def _series(self, name: str, labels: Dict[str, str]) -> _Series:
        entry = self._metrics.get(name)
        if entry is None:
            spec = CATALOG.get(name)
            if spec is None:
                raise KeyError(
                    f"metric {name!r} is not declared in obs.metrics."
                    "CATALOG — add it there (and to docs/OBSERVABILITY"
                    ".md; scripts/check_metric_names.py enforces both)"
                )
            with self._lock:
                entry = self._metrics.get(name)
                if entry is None:
                    entry = (spec, {}, threading.Lock())
                    self._metrics[name] = entry
        spec, series, lock = entry
        if labels:
            allowed = spec["labels"]
            for k in labels:
                if k not in allowed:
                    raise KeyError(
                        f"label {k!r} not declared for metric {name!r} "
                        f"(allowed: {allowed})"
                    )
            key = tuple(sorted((k, str(v)) for k, v in labels.items()))
        else:
            key = ()
        s = series.get(key)
        if s is None:
            with lock:
                s = series.get(key)
                if s is None:
                    if key and len(series) >= self.max_label_sets:
                        # cardinality cap: collapse into the reserved
                        # overflow series (created on first breach).
                        # The counter is registry-wide, so guard it
                        # with the registry lock — two metrics
                        # overflowing concurrently hold DIFFERENT
                        # series locks (lock order metric→registry is
                        # safe: creation never takes them nested the
                        # other way)
                        with self._lock:
                            self._label_overflows += 1
                        s = series.get(_OVERFLOW_KEY)
                        if s is None:
                            s = series[_OVERFLOW_KEY] = _Series(
                                _OVERFLOW_KEY,
                                len(spec.get("buckets", ())) + 1
                                if spec["type"] == HISTOGRAM else 0,
                            )
                        return s
                    s = series[key] = _Series(
                        key,
                        len(spec.get("buckets", ())) + 1
                        if spec["type"] == HISTOGRAM else 0,
                    )
        return s

    # -- write surface -------------------------------------------------------

    def inc(self, name: str, value: float = 1.0, **labels: str) -> None:
        s = self._series(name, labels)
        with self._metrics[name][2]:
            s.value += value

    def set_gauge(self, name: str, value: float, **labels: str) -> None:
        s = self._series(name, labels)
        lock = self._metrics[name][2]
        with lock:
            s.value = float(value)

    def observe(self, name: str, value: float, **labels: str) -> None:
        spec = CATALOG.get(name)
        if spec is None or spec["type"] != HISTOGRAM:
            raise KeyError(f"{name!r} is not a cataloged histogram")
        s = self._series(name, labels)
        lock = self._metrics[name][2]
        buckets = spec["buckets"]
        # bisect_left = first bound >= value, i.e. Prometheus le
        # semantics; index len(buckets) is the +Inf bucket
        i = bisect_left(buckets, value)
        with lock:
            s.bucket_counts[i] += 1
            s.sum += value
            s.count += 1

    # -- read surface (lock-free) --------------------------------------------

    def get(self, name: str, **labels: str) -> Optional[float]:
        """Current value of one counter/gauge series (None when the
        series does not exist yet)."""
        entry = self._metrics.get(name)
        if entry is None:
            return None
        key = tuple(sorted((k, str(v)) for k, v in labels.items()))
        s = entry[1].get(key)
        return s.value if s is not None else None

    def get_histogram(self, name: str, **labels: str) -> Optional[dict]:
        """Live view of one histogram series (None when the series
        does not exist yet): bucket bounds, per-bucket counts, sum,
        count.  Lock-free like :meth:`get` — a read racing an observe
        may be one tick stale on one bucket, never torn across the
        registry.  The SLO controller diffs two of these to get a
        WINDOWED latency distribution."""
        entry = self._metrics.get(name)
        if entry is None:
            return None
        spec = entry[0]
        if spec["type"] != HISTOGRAM:
            raise KeyError(f"{name!r} is not a cataloged histogram")
        key = tuple(sorted((k, str(v)) for k, v in labels.items()))
        s = entry[1].get(key)
        if s is None:
            return None
        return {
            "bounds": list(spec["buckets"]),
            "buckets": list(s.bucket_counts),
            "sum": s.sum,
            "count": s.count,
        }

    def label_overflows(self) -> int:
        """WRITES that landed on an overflow series (not distinct
        evicted label sets — telling those apart would require storing
        exactly the keys the cap exists to bound).  Nonzero means some
        metric's labels exceeded ``max_label_sets``; the rate says how
        hot the overflowing series are, not how many there were."""
        return self._label_overflows

    def snapshot(self) -> Dict[str, Any]:
        """Point-in-time copy of every live series — readers never take
        the write locks (see module docstring, constraint 2)."""
        out: Dict[str, Any] = {}
        for name, (spec, series, _lock) in list(self._metrics.items()):
            rows = []
            for s in list(series.values()):
                row: Dict[str, Any] = {"labels": dict(s.labels)}
                if spec["type"] == HISTOGRAM:
                    row["buckets"] = list(s.bucket_counts)
                    row["sum"] = s.sum
                    row["count"] = s.count
                else:
                    row["value"] = s.value
                rows.append(row)
            out[name] = {
                "type": spec["type"],
                "help": spec["help"],
                "series": rows,
            }
            if spec["type"] == HISTOGRAM:
                out[name]["bucket_bounds"] = list(spec["buckets"])
        return out

    # -- exposition ----------------------------------------------------------

    @staticmethod
    def _fmt_labels(labels, extra: str = "") -> str:
        parts = [
            '%s="%s"' % (
                k,
                str(v).replace("\\", r"\\").replace('"', r"\"")
                .replace("\n", r"\n"),
            )
            for k, v in labels
        ]
        if extra:
            parts.append(extra)
        return "{%s}" % ",".join(parts) if parts else ""

    @staticmethod
    def _fmt_value(v: float) -> str:
        return repr(int(v)) if float(v).is_integer() else repr(v)

    def to_prometheus(self) -> str:
        """Prometheus text exposition (format 0.0.4) of every live
        series, metrics sorted by name for diffable output."""
        lines: List[str] = []
        for name in sorted(self._metrics):
            spec, series, _lock = self._metrics[name]
            lines.append(f"# HELP {name} {spec['help']}")
            lines.append(f"# TYPE {name} {spec['type']}")
            for s in sorted(
                list(series.values()), key=lambda s: s.labels
            ):
                if spec["type"] == HISTOGRAM:
                    # snapshot the counts once so the cumulative sums
                    # below cannot tear against concurrent observes
                    counts = list(s.bucket_counts)
                    acc = 0
                    for bound, n in zip(spec["buckets"], counts):
                        acc += n
                        lines.append(
                            f"{name}_bucket"
                            + self._fmt_labels(
                                s.labels, f'le="{bound}"'
                            )
                            + f" {acc}"
                        )
                    acc += counts[-1]
                    lines.append(
                        f"{name}_bucket"
                        + self._fmt_labels(s.labels, 'le="+Inf"')
                        + f" {acc}"
                    )
                    lines.append(
                        f"{name}_sum" + self._fmt_labels(s.labels)
                        + f" {self._fmt_value(s.sum)}"
                    )
                    lines.append(
                        f"{name}_count" + self._fmt_labels(s.labels)
                        + f" {acc}"
                    )
                else:
                    lines.append(
                        name + self._fmt_labels(s.labels)
                        + f" {self._fmt_value(s.value)}"
                    )
        return "\n".join(lines) + ("\n" if lines else "")

    def write_prometheus(self, path: str) -> str:
        """Atomically (tmp + rename) publish the Prometheus text dump —
        a scraper/tailer never reads a torn snapshot."""
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(self.to_prometheus())
        os.replace(tmp, path)  # storage: telemetry
        return path

    def write_jsonl(self, path: str) -> Dict[str, Any]:
        """Append one snapshot record (wall + monotonic timestamps from
        the injectable clocks) to a JSONL file and return it."""
        record = {
            "ts": self._clock(),
            "mono": self._mono(),
            "seq": self._jsonl_records,
            "metrics": self.snapshot(),
        }
        self._jsonl_records += 1
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "a") as f:  # storage: unbounded(caller-owned JSONL export path)
            f.write(json.dumps(record) + "\n")
        return record


# ---------------------------------------------------------------------------
# the process default registry + module-level write helpers (hot paths
# call these; swap the default out with set_registry for test isolation)
# ---------------------------------------------------------------------------

_default = MetricsRegistry()


def registry() -> MetricsRegistry:
    return _default


def set_registry(r: MetricsRegistry) -> MetricsRegistry:
    """Replace the process default registry; returns the previous one."""
    global _default
    prev, _default = _default, r
    return prev


def reset_registry() -> MetricsRegistry:
    """Fresh default registry (test isolation); returns the new one."""
    set_registry(MetricsRegistry())
    return _default


def inc(name: str, value: float = 1.0, **labels: str) -> None:
    _default.inc(name, value, **labels)


def set_gauge(name: str, value: float, **labels: str) -> None:
    _default.set_gauge(name, value, **labels)


def observe(name: str, value: float, **labels: str) -> None:
    _default.observe(name, value, **labels)
