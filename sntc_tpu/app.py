"""Application entry points — the reference's L0 script layer.

Behavioral spec: SURVEY.md §2.1: the reference app is a set of driver
scripts over CICIDS2017 day CSVs — per-estimator train/eval scripts
(`[R]`, capability fixed by [B:6-12]) and a streaming-inference script
([B:11]).  This module is their CLI equivalent:

    python -m sntc_tpu synth    --out data/ --rows 100000
    python -m sntc_tpu train    --data data/ --estimator mlp --model-out m/
    python -m sntc_tpu evaluate --data data/ --model m/ --metric macroF1
    python -m sntc_tpu serve    --model m/ --watch data/in --out data/out \
                                --checkpoint data/ckpt

``train`` assembles the same pipeline shapes the five bench configs use
(StringIndexer → VectorAssembler → [StandardScaler] → estimator);
``serve`` runs the micro-batch engine over a watched CSV directory with
offset/commit resume.  Real "MachineLearningCVE" day CSVs drop in
unchanged; ``synth`` writes schema-identical synthetic days.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

TRAIN_DEFAULT_LAYERS = "78,64,15"


def _device_fields() -> dict:
    """``platform`` / ``device_kind`` / ``device_count`` for a command's
    JSON result line (see ``parallel.mesh.device_report``)."""
    from sntc_tpu.parallel.mesh import device_report

    return device_report()


def _obs_start(args) -> None:
    """Arm the telemetry surfaces a command requested (before any
    work): ``--trace-out`` enables the span tracer for the process."""
    if getattr(args, "trace_out", None):
        from sntc_tpu.obs import enable_tracing

        enable_tracing()


def _obs_finish(args) -> None:
    """Publish the telemetry a command requested: the Prometheus text
    snapshot (``--metrics-out``, atomic) and the Chrome-trace/Perfetto
    span export (``--trace-out``)."""
    if getattr(args, "metrics_out", None):
        from sntc_tpu.obs import registry

        registry().write_prometheus(args.metrics_out)
    if getattr(args, "trace_out", None):
        from sntc_tpu.obs import tracer

        t = tracer()
        if t is not None:
            t.export_chrome_trace(args.trace_out)


def _device_trace_ctx(args):
    """``--device-trace DIR``: a jax.profiler capture around the run
    (XLA op timeline for Perfetto/TensorBoard) — device time next to
    the host spans.  A no-op context when the flag is unset."""
    if getattr(args, "device_trace", None):
        from sntc_tpu.obs import device_trace

        return device_trace(args.device_trace)
    return contextlib.nullcontext()


def _add_obs_flags(p, device: bool = True):
    p.add_argument("--metrics-out", default=None, metavar="PATH",
                   help="write the process metrics registry as a "
                   "Prometheus text snapshot here (atomic; "
                   "serve-daemon republishes it every scheduling "
                   "round, other commands at exit) — see "
                   "docs/OBSERVABILITY.md")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="arm the span tracer and export the host-stage "
                   "timeline as Chrome-trace JSON here at exit "
                   "(loadable in chrome://tracing / ui.perfetto.dev)")
    if device:
        p.add_argument("--device-trace", default=None, metavar="DIR",
                       help="additionally capture a jax.profiler "
                       "(XLA op-level) trace of the run into DIR "
                       "for TensorBoard/Perfetto")


def _build_estimator(name: str, mesh, args):
    from sntc_tpu.models import (
        DecisionTreeClassifier,
        GBTClassifier,
        LinearSVC,
        LogisticRegression,
        MultilayerPerceptronClassifier,
        NaiveBayes,
        OneVsRest,
        RandomForestClassifier,
    )

    if name == "lr":
        return LogisticRegression(
            mesh=mesh, maxIter=args.max_iter, regParam=args.reg_param
        )
    if name == "mlp":
        layers = [int(v) for v in args.layers.split(",")]
        return MultilayerPerceptronClassifier(
            mesh=mesh, layers=layers, maxIter=args.max_iter, seed=args.seed
        )
    if name == "rf":
        return RandomForestClassifier(
            mesh=mesh, numTrees=args.num_trees, maxDepth=args.max_depth,
            seed=args.seed,
        )
    if name == "gbt":
        return OneVsRest(
            classifier=GBTClassifier(
                mesh=mesh, maxIter=args.max_iter, maxDepth=args.max_depth,
                stepSize=args.step_size, seed=args.seed,
                maxBins=args.max_bins,
            ),
            featuresCol=args.features_col,
        )
    if name == "dt":
        return DecisionTreeClassifier(
            mesh=mesh, maxDepth=args.max_depth, maxBins=args.max_bins,
            seed=args.seed,
        )
    if name == "nb":
        return NaiveBayes(mesh=mesh, modelType="gaussian")
    if name == "svc":
        return OneVsRest(
            classifier=LinearSVC(
                mesh=mesh, maxIter=args.max_iter, regParam=args.reg_param
            ),
            featuresCol=args.features_col,
        )
    raise SystemExit(
        f"unknown estimator {name!r} (lr|mlp|rf|gbt|dt|nb|svc)"
    )


def _feature_stages(mesh, args, with_scaler: bool):
    from sntc_tpu.data import CICIDS2017_FEATURES
    from sntc_tpu.feature import (
        ChiSqSelector,
        StandardScaler,
        StringIndexer,
        VectorAssembler,
    )

    stages = [
        StringIndexer(inputCol=args.label_col, outputCol="label",
                      handleInvalid="skip"),
        VectorAssembler(inputCols=CICIDS2017_FEATURES,
                        outputCol="rawFeatures", handleInvalid="skip"),
    ]
    if args.chisq_top:
        stages.append(ChiSqSelector(
            mesh=mesh, numTopFeatures=args.chisq_top,
            featuresCol="rawFeatures", labelCol="label",
            outputCol=args.features_col,
        ))
    elif with_scaler:
        stages.append(StandardScaler(
            mesh=mesh, inputCol="rawFeatures", outputCol=args.features_col,
            withMean=True,
        ))
    return stages


def _load_data(args):
    from sntc_tpu.data import clean_flows, load_csv_dir

    df = clean_flows(load_csv_dir(args.data))
    if args.binary:
        import numpy as np

        df = df.with_column(
            args.label_col,
            np.where(
                df[args.label_col].astype(str) == "BENIGN", "benign", "attack"
            ).astype(object),
        )
    return df


def cmd_train(args) -> int:
    from sntc_tpu.parallel.context import get_default_mesh

    _obs_start(args)
    mesh = get_default_mesh()
    # telemetry publishes in finally: a crashed fit is exactly the run
    # whose partial metrics/spans the operator armed --metrics-out /
    # --trace-out to see (same contract as the serve/daemon paths)
    try:
        return _cmd_train_body(args, mesh)
    finally:
        _obs_finish(args)


def _cmd_train_body(args, mesh) -> int:
    from sntc_tpu.core.base import Pipeline
    from sntc_tpu.data import CICIDS2017_FEATURES
    from sntc_tpu.evaluation import MulticlassClassificationEvaluator
    from sntc_tpu.mlio import save_model
    from sntc_tpu.obs import span

    with span("train.load_data"):
        df = _load_data(args)
    train, test = df.random_split(
        [1 - args.test_fraction, args.test_fraction], seed=args.seed
    )
    with_scaler = args.estimator in ("lr", "mlp", "svc")
    # the column the estimator reads = whatever the LAST feature stage
    # writes: chisq/scaler write --features-col, a bare assembler leaves
    # "rawFeatures" (trees consume unscaled features, as the reference does)
    if not args.chisq_top and not with_scaler:
        args.features_col = "rawFeatures"
    n_features = args.chisq_top or len(CICIDS2017_FEATURES)
    if args.estimator == "mlp":
        import numpy as np

        n_classes = int(np.unique(train[args.label_col].astype(str)).size)
        layers = [int(v) for v in args.layers.split(",")]
        is_default = args.layers == TRAIN_DEFAULT_LAYERS
        for pos, want, what in (
            (0, n_features, "input width / feature count"),
            (-1, n_classes, "output width / class count"),
        ):
            if layers[pos] != want:
                if is_default:
                    layers[pos] = want  # default layers track the data
                else:
                    raise SystemExit(
                        f"--layers {what} mismatch: {layers[pos]} != {want}"
                    )
        args.layers = ",".join(str(v) for v in layers)
    est = _build_estimator(args.estimator, mesh, args)
    if est.hasParam("featuresCol"):
        est.set("featuresCol", args.features_col)
    pipe = Pipeline(stages=_feature_stages(mesh, args, with_scaler) + [est])
    t0 = time.perf_counter()
    with _device_trace_ctx(args), span(
        "train.fit", estimator=args.estimator
    ):
        model = pipe.fit(train)
    fit_s = time.perf_counter() - t0
    with span("train.evaluate"):
        f1 = MulticlassClassificationEvaluator(
            metricName=args.metric, mesh=mesh
        ).evaluate(model.transform(test))
    if args.model_out:
        save_model(model, args.model_out)
    print(json.dumps({
        "estimator": args.estimator, "train_rows": train.num_rows,
        "fit_wall_clock_s": round(fit_s, 3), args.metric: f1,
        "model_out": args.model_out, **_device_fields(),
    }))
    return 0


def cmd_evaluate(args) -> int:
    from sntc_tpu.evaluation import MulticlassClassificationEvaluator
    from sntc_tpu.mlio import load_model
    from sntc_tpu.parallel.context import get_default_mesh

    mesh = get_default_mesh()
    model = load_model(args.model)
    df = _load_data(args)
    value = MulticlassClassificationEvaluator(
        metricName=args.metric, mesh=mesh
    ).evaluate(model.transform(df))
    print(json.dumps({
        "rows": df.num_rows, args.metric: value, **_device_fields(),
    }))
    return 0


def strip_label_indexer(model, label_index_col: str):
    """Serving prep: remove the LABEL indexing (live flows carry no
    label column) while KEEPING any feature-column indexing, and return
    the label vocabulary for mapping predictions back to strings.

    Handles both indexer modes: a single-column StringIndexerModel
    writing ``label_index_col`` is dropped whole; a multi-column one is
    reduced to its non-label columns.  Returns ``(stages, labels)``
    where ``labels`` is None when no label indexer was found."""
    from sntc_tpu.feature.string_indexer import (
        StringIndexerModel,
        _resolve_cols,
    )

    stages, labels = [], None
    for s in model.getStages():
        if isinstance(s, StringIndexerModel):
            ins, outs = _resolve_cols(s)
            if label_index_col in outs:
                j = outs.index(label_index_col)
                labels = s.labelsArray[j]
                keep = [k for k in range(len(outs)) if k != j]
                if keep:
                    reduced = StringIndexerModel(
                        labelsArray=[s.labelsArray[k] for k in keep],
                    )
                    reduced.setParams(
                        inputCols=[ins[k] for k in keep],
                        outputCols=[outs[k] for k in keep],
                        handleInvalid=s.getHandleInvalid(),
                        stringOrderType=s.getStringOrderType(),
                    )
                    stages.append(reduced)
                continue
        stages.append(s)
    return stages, labels


def _serving_form(model, label_index_col: str, fuse: bool,
                  fuse_heads: bool = True):
    """One checkpoint → its servable form, shared by ``serve`` and
    ``serve-daemon``: drop the LABEL indexer (live flows carry no
    label; feature-column indexers are kept), map predictions back to
    label strings with its vocabulary, and — with ``fuse`` — compile
    through the whole-pipeline fusion compiler
    (docs/PERFORMANCE.md "Whole-pipeline fusion"; ``fuse_heads=False``
    keeps the head a plain swappable stage for lifecycle hot-swap).
    Returns ``(model, labels, out_cols)``."""
    from sntc_tpu.core.base import PipelineModel

    out_cols = ["prediction"]
    labels = None
    if isinstance(model, PipelineModel):
        from sntc_tpu.feature import IndexToString
        from sntc_tpu.serve import compile_serving

        stages, labels = strip_label_indexer(model, label_index_col)
        tail = (
            [IndexToString(
                inputCol="prediction", outputCol="predictedLabel",
                labels=labels,
            )]
            if labels is not None else []
        )
        model = PipelineModel(stages=stages + tail)
        if fuse:
            model = compile_serving(model, fuse_heads=fuse_heads)
        if tail:
            out_cols = ["prediction", "predictedLabel"]
    return model, labels, out_cols


def cmd_serve(args) -> int:
    from sntc_tpu.mlio import load_model
    from sntc_tpu.resilience import (
        QuerySupervisor,
        RetryPolicy,
        default_breakers,
    )
    from sntc_tpu.serve import (
        CsvDirSink,
        FileStreamSource,
        StreamingQuery,
    )

    _obs_start(args)
    # kernel tier selection must land before any serving compile reads
    # it (the registry re-resolves per dispatch, but the journaled run
    # config should reflect one consistent mode end-to-end)
    if getattr(args, "serve_kernels", None):
        os.environ["SNTC_SERVE_KERNELS"] = args.serve_kernels
    model = load_model(args.model)
    raw_model = model  # persistable form: the lifecycle publish target
    # model lifecycle (r11): any of the drift / shadow-promotion /
    # incremental-fit flags arms the LifecycleManager on the engine
    lifecycle_armed = bool(
        args.partial_fit or args.drift_window > 0 or args.promote_from
    )
    # only a config that can SWAP models needs the head kept out of the
    # fused segments; drift-only monitoring keeps full head fusion
    swap_armed = bool(args.partial_fit or args.promote_from)
    # no labels on live flows: the label indexer comes off and
    # predictions map back to label STRINGS — the reference app's
    # output shape.  --fuse (default) compiles through the whole-
    # pipeline fusion compiler; with promotion or partial-fit armed
    # the HEAD stays a plain stage (fuse_heads=False): a fused head's
    # weights are constants of the segment's program, so hot-swapping
    # it would recompile the whole prefix — plain heads swap with zero
    # prefix recompiles while the feature prefix still fuses.
    # Drift-only monitoring never swaps, so it keeps full fusion.
    model, labels, out_cols = _serving_form(
        model, args.label_index_col, args.fuse,
        fuse_heads=not swap_armed,
    )
    # a SERVED query degrades instead of dying: transient read/sink
    # errors retry in place, a batch that keeps failing quarantines to
    # the dead-letter journal after --max-batch-failures rounds, and
    # the breakers get enough outcomes to actually open — without these
    # the first IOError would kill the process and the supervision
    # layer below would never see a second chance
    retries = max(1, args.batch_retry_attempts)
    # pipelined serving (docs/PERFORMANCE.md): depth > 1 arms the
    # overlapped retire stage (sink delivery on its own thread) and the
    # source's background prefetch; --shape-buckets pads micro-batches
    # to power-of-two row buckets so predict compiles once per bucket
    pipelined = args.pipeline_depth > 1
    # --row-policy salvage|permissive arms the data-plane admission
    # layer against the canonical CICIDS2017 contract: poison ROWS are
    # excised (and journaled to <checkpoint>/dead_letter_rows/ with
    # file/line/raw/reason) while the clean rows keep serving — and the
    # CSV parser itself salvages ragged lines instead of failing the
    # batch.  "strict" keeps today's trust-the-input behavior: the
    # whole batch fails and the poison-batch machinery owns it.
    contract = None
    if args.row_policy != "strict":
        from sntc_tpu.data import CICIDS2017_CONTRACT

        contract = CICIDS2017_CONTRACT.with_mode(args.row_policy)
    # live-model lifecycle: --drift-window arms the divergence monitor
    # (drift_detected events, model DEGRADED); --promote-from shadow-
    # scores a candidate checkpoint and promotes it through the atomic
    # publish + between-batches hot-swap; --partial-fit incrementally
    # refits the candidate head from live labeled batches (LR/NB)
    lifecycle = None
    if lifecycle_armed:
        from sntc_tpu.lifecycle import (
            DriftMonitor,
            LifecycleManager,
            ModelPromoter,
        )

        drift = None
        if args.drift_window > 0:
            drift = DriftMonitor(
                window=args.drift_window,
                threshold=args.drift_threshold,
            ).attach()
        promoter = None
        if args.promote_from or args.partial_fit:
            promoter = ModelPromoter(
                model,
                incumbent_raw=raw_model,
                serving_path=args.model,
                checkpoint_dir=args.checkpoint,
                window=args.shadow_window,
                margin=args.promote_margin,
                label_col="Label",
                labels=labels,
                bucket_rows=args.shape_buckets,
            )
            if args.partial_fit:
                from sntc_tpu.lifecycle import (
                    incremental_estimator_for,
                    terminal_head,
                )

                try:  # fail fast on a head with no partial_fit path
                    incremental_estimator_for(terminal_head(model))
                except ValueError as e:
                    raise SystemExit(f"--partial-fit: {e}")
            if args.promote_from:
                promoter.load_candidate(args.promote_from)
        lifecycle = LifecycleManager(
            drift=drift,
            promoter=promoter,
            partial_fit=args.partial_fit,
            n_classes=len(labels) if labels is not None else None,
        )
    # --from-capture (flow subsystem): the watch directory holds RAW
    # pcap/NetFlow capture files; a stateful keyed-window operator
    # computes the CICIDS2017 flow features live (watermark-driven
    # windows, crash-safe snapshot-at-commit state under
    # <checkpoint>/flow_state) and the emitted feature rows ride the
    # SAME admission → predict → sink path the CSV mode serves.  See
    # docs/RESILIENCE.md "Stateful flow windows".
    # --listen-udp / --listen-tcp (r20): the live network front door.
    # The watch directory becomes the ingress SPOOL: a supervised
    # listener seals socket payloads (NetFlow v5 datagrams over UDP,
    # length-prefixed CSV rows over TCP) into replayable capture files
    # there, and the engine serves the sealed files through the
    # ordinary directory-source machinery — WAL replay, admission, the
    # autotuner and the SLO controller all compose unchanged.  See
    # docs/RESILIENCE.md "Network ingress".
    ingress_listeners = []
    if args.listen_udp is not None or args.listen_tcp is not None:
        from sntc_tpu.serve import ingress as _ingress

        if args.from_capture:
            raise SystemExit(
                "--listen-udp/--listen-tcp spool their own capture "
                "format; drop --from-capture (UDP serves NetFlow v5 "
                "directly)"
            )
        ingress_columns = None
        if args.listen_tcp is not None:
            # framed TCP rows carry VALUES only; the sealed CSV files
            # need a header naming them — the admission contract's
            # column order is the wire contract
            from sntc_tpu.data import CICIDS2017_CONTRACT

            ingress_columns = list(
                (contract or CICIDS2017_CONTRACT).columns
            )
        source, ingress_listeners = _ingress.build_ingress(
            args.watch,
            listen_udp=args.listen_udp,
            listen_tcp=args.listen_tcp,
            spool_mb=args.ingress_spool_mb,
            columns=ingress_columns,
            source_kwargs=dict(
                prefetch_batches=(
                    args.prefetch_batches if pipelined else 0
                ),
                read_workers=args.read_workers,
                parse_salvage=contract is not None,
            ),
        )
    elif args.from_capture:
        from sntc_tpu.flow import FlowCaptureSource

        source = FlowCaptureSource(
            args.watch,
            format=args.from_capture,
            flow_timeout=args.flow_timeout,
            activity_timeout=args.flow_activity_timeout,
            allowed_lateness=args.flow_lateness,
            max_state_packets=args.flow_max_packets,
            state_dir=os.path.join(args.checkpoint, "flow_state"),
            prefetch_batches=(args.prefetch_batches if pipelined else 0),
            read_workers=args.read_workers,
        )
    else:
        source = FileStreamSource(
            args.watch,
            prefetch_batches=(args.prefetch_batches if pipelined else 0),
            read_workers=args.read_workers,
            parse_salvage=contract is not None,
        )
    # closed-loop SLO control (r16): any --slo-* flag declares a
    # setpoint and arms the ServeController over this engine via the
    # supervisor below (--no-controller keeps the knobs at their flag
    # values).  Resolved HERE because the controller OWNS the ingest
    # tuner — one owner per knob, exactly the daemon rule.
    slo = None
    if args.controller and (
        args.slo_p99_ms or args.slo_min_rows_per_sec
        or args.slo_max_shed_rate
    ):
        from sntc_tpu.serve import SloPolicy

        slo = SloPolicy(
            slo_p99_ms=args.slo_p99_ms,
            slo_min_rows_per_sec=args.slo_min_rows_per_sec,
            slo_max_shed_rate=args.slo_max_shed_rate,
        )
    # --autotune: the ingest source graph tunes its own pools/queues
    # (read_workers, prefetch width, pipeline depth) from observed
    # stage latencies, with hysteresis and journaled decisions —
    # tf.data AUTOTUNE for this serve path (docs/PERFORMANCE.md
    # "Autotuned ingest"); the flags above become the cold-start
    # values.  With SLOs declared the CONTROLLER owns the tuner (and
    # pipeline_depth) — an engine-owned tuner alongside it would
    # double-steer the same knobs with two direction histories and
    # defeat the no-oscillation bound.
    autotuner = None
    if args.autotune and slo is None:
        from sntc_tpu.data.autotune import IngestAutotuner

        autotuner = IngestAutotuner()
    # compute-plane fault domain (r18, default armed): device/XLA
    # errors classify and respond per kind — OOM splits the
    # micro-batch, a failed/over-budget compile poisons its signature
    # onto the host fallback, a lost device flips HOST_DEGRADED with
    # probe-gated recovery — instead of riding the generic poison-batch
    # machinery.  The pre-built predictor carries the domain into the
    # engine (and every fused segment).
    if args.device_faults:
        from sntc_tpu.resilience.device import (
            DeviceFaultDomain,
            DevicePolicy,
        )
        from sntc_tpu.serve import BatchPredictor

        model = BatchPredictor(
            model,
            bucket_rows=args.shape_buckets,
            device_domain=DeviceFaultDomain(DevicePolicy(
                compile_budget_s=args.compile_budget_s or None,
            )),
        )
    q = StreamingQuery(
        model,
        source,
        CsvDirSink(args.out, columns=out_cols),
        args.checkpoint,
        max_batch_offsets=args.max_files_per_batch,
        pipeline_depth=args.pipeline_depth,
        shape_buckets=args.shape_buckets,
        overlap_sink=pipelined,
        breakers=default_breakers(),
        retry_policy=(
            RetryPolicy(max_attempts=retries, base_delay_s=0.2, jitter=0.1)
            if retries > 1 else None
        ),
        max_batch_failures=(
            args.max_batch_failures if args.max_batch_failures > 0 else None
        ),
        schema_contract=contract,
        row_dead_letter_dir=args.row_dead_letter,
        lifecycle=lifecycle,
        autotuner=autotuner,
        wal_mode=args.wal_mode,
        wal_compact_every=args.wal_compact_every,
        wal_keep_commits=args.wal_keep_commits,
        dead_letter_keep=args.dead_letter_keep,
    )
    repl_plane = None
    if args.standby_root:
        # warm-standby disaster recovery (r23): ship the checkpoint's
        # durable tree + the sink to the standby root and seal a
        # commit barrier every --repl-barrier-every commits
        from sntc_tpu.resilience.replicate import ReplicationPlane

        repl_plane = ReplicationPlane(
            args.checkpoint, args.standby_root,
            barrier_every=args.repl_barrier_every,
            sink_dir=args.out,
        )
        q.commit_listener = repl_plane.on_commit
    if ingress_listeners:
        from sntc_tpu.serve import ingress as _ingress

        # retention prunes only BELOW the committed horizon, and the
        # listeners go live only once the engine that replays their
        # spool exists
        _ingress.wire_committed_offset(source, q.committed_end)
        for l in ingress_listeners:
            l.start()
    if args.once:
        try:
            with _device_trace_ctx(args):
                n = q.process_available()
                if ingress_listeners:
                    # settle the front door (intake stops, tail seals),
                    # then serve what it sealed — '--once' means the
                    # spool is drained too
                    for l in ingress_listeners:
                        l.drain()
                    n += q.process_available()
                if repl_plane is not None:
                    repl_plane.close()
        finally:
            # publish even when the drain crashed — the partial
            # metrics/trace are the debugging evidence
            _obs_finish(args)
        print(json.dumps({"batches": n, **_device_fields()}))
        return 0
    # supervised loop: SIGTERM (and Ctrl-C) drains — finish in-flight
    # batches, commit, write drain_marker.json — and exits 0; a restart
    # on the same checkpoint resumes exactly-once from the offset log
    # the controller (slo resolved above, before the autotuner) steers
    # --pipeline-depth / --shape-buckets / the shed knob live and
    # journals every decision to <checkpoint>/controller.jsonl — see
    # docs/RESILIENCE.md "Closed-loop SLO control"
    sup = QuerySupervisor(
        q,
        max_pending_batches=args.max_pending_batches,
        shed_policy=args.shed_policy,
        max_batch_wall_time=args.max_batch_wall_time,
        health_json=args.health_json,
        slo=slo,
        disk_budget_mb=args.disk_budget_mb,
    )
    sup.install_signal_handlers()
    if ingress_listeners:
        # SIGTERM settles the FRONT DOOR first — intake stops and the
        # ring tail seals durably — and only then requests the engine
        # drain, so nothing a sender was acked (the sealed file) can
        # die in listener memory
        import signal as _signal

        def _drain_ingress_then_engine(signum, frame):
            for l in ingress_listeners:
                try:
                    l.drain()
                except Exception:
                    pass
            sup.request_drain("SIGTERM")

        _signal.signal(_signal.SIGTERM, _drain_ingress_then_engine)
    print(f"serving: watching {args.watch} -> {args.out} "
          f"(checkpoint {args.checkpoint}); SIGTERM/Ctrl-C drains",
          file=sys.stderr)
    try:
        with _device_trace_ctx(args):
            status = sup.run(poll_interval=args.poll_interval)
    except KeyboardInterrupt:
        status = sup.drain_now("KeyboardInterrupt")
    finally:
        for l in ingress_listeners:
            try:
                l.close()
            except Exception:
                pass
        if repl_plane is not None:
            repl_plane.close()
        sup.close()  # unsubscribe the health monitor from the event bus
        _obs_finish(args)
    print(json.dumps({
        "batches": status["engine"]["batches_done"],
        "drained": status["drained"],
        "health": status["health"]["overall"],
        **_device_fields(),
    }))
    return 0


def cmd_serve_daemon(args) -> int:
    """Multi-tenant serving: N tenant streams (pipeline + source +
    sink + checkpoint + row policy each) multiplexed over one shared
    device program cache with fair scheduling and per-tenant fault
    isolation — see docs/RESILIENCE.md "Multi-tenant serving".

    The tenant file (``--tenants``) is JSON: ``{"tenants": [{"id":
    ..., "model": <checkpoint>, "watch": <in dir>, "out": <out dir>,
    ...}]}`` where every entry may override the daemon-level default
    flags (``weight``, ``max_rows_per_sec``, ``max_pending_batches``,
    ``shed_policy``, ``quarantine_after``, ``quarantine_cooldown_s``,
    ``stop_after``, ``row_policy``, ...).  Tenants naming the SAME
    model checkpoint share one predictor — and therefore one set of
    compiled device programs."""
    from sntc_tpu.serve import ServeDaemon

    _obs_start(args)
    specs = _load_tenant_specs(args)
    daemon = ServeDaemon(
        specs, args.root,
        shape_buckets=args.shape_buckets,
        pipeline_depth=args.pipeline_depth,
        health_json=args.health_json,
        metrics_out=args.metrics_out,
        autotune=args.autotune,
        controller=args.controller,
        disk_budget_mb=args.root_disk_budget_mb,
        dead_letter_keep=args.dead_letter_keep,
        device_faults=args.device_faults,
        compile_budget_s=args.compile_budget_s or None,
        standby_root=args.standby_root,
        repl_barrier_every=args.repl_barrier_every,
    )
    try:
        if args.once:
            with _device_trace_ctx(args):
                n = daemon.process_available()
            # the --once pass IS the warmup; the drain that follows
            # must not compile anything new on the shared cache
            daemon.mark_warm()
            daemon.drain()
            status = daemon.status()
        else:
            daemon.install_signal_handlers()
            print(
                f"serve-daemon: {len(specs)} tenants -> {args.root}; "
                "SIGTERM/Ctrl-C drains every tenant",
                file=sys.stderr,
            )
            try:
                with _device_trace_ctx(args):
                    status = daemon.run(
                        poll_interval=args.poll_interval
                    )
            except KeyboardInterrupt:
                daemon.request_drain("KeyboardInterrupt")
                daemon.drain()
                status = daemon.status()
            n = status["aggregate"]["batches_done"]
    finally:
        daemon.close()
        _obs_finish(args)
    print(json.dumps({
        "batches": n,
        "tenants": {
            tid: row["state"] for tid, row in status["tenants"].items()
        },
        "recompiles_after_warmup": status["recompiles_after_warmup"],
        "drained": status["drained"],
        "health": status["health"]["overall"],
        **_device_fields(),
    }))
    return 0


def _load_tenant_specs(args) -> list:
    """The serve-daemon / fleet-serve tenant catalog: parse the
    ``--tenants`` JSON, load + compile each DISTINCT model checkpoint
    once, apply the flag-level defaults, and return the TenantSpec
    list."""
    from sntc_tpu.mlio import load_model
    from sntc_tpu.resilience import RetryPolicy
    from sntc_tpu.serve import TenantSpec

    with open(args.tenants) as f:
        doc = json.load(f)
    entries = doc["tenants"] if isinstance(doc, dict) else doc
    if not entries:
        raise SystemExit(f"{args.tenants}: no tenants declared")
    retries = max(1, args.batch_retry_attempts)
    defaults = {
        "weight": args.tenant_weight,
        "max_rows_per_sec": args.max_rows_per_sec,
        "max_pending_batches": args.max_pending_batches,
        "shed_policy": args.shed_policy,
        "quarantine_after": args.quarantine_after,
        "quarantine_cooldown_s": args.quarantine_cooldown,
        "stop_after": args.stop_after,
        "from_capture": args.from_capture,
        "slo_p99_ms": args.slo_p99_ms,
        "slo_min_rows_per_sec": args.slo_min_rows_per_sec,
        "slo_max_shed_rate": args.slo_max_shed_rate,
        "disk_budget_mb": args.disk_budget_mb,
        "max_batch_offsets": args.max_files_per_batch,
        "max_batch_failures": (
            args.max_batch_failures if args.max_batch_failures > 0
            else None
        ),
        "retry_policy": (
            RetryPolicy(max_attempts=retries, base_delay_s=0.2,
                        jitter=0.1)
            if retries > 1 else None
        ),
        # live network front door (r20): daemon-level listener flags
        # become the default per-tenant ingress block (a tenant's own
        # 'ingress' JSON block replaces it wholesale); port 0 gives
        # every tenant its own ephemeral port, published in its
        # <watch>/ingress_stats.json
        "ingress": (
            {
                "listen_udp": args.listen_udp,
                "listen_tcp": args.listen_tcp,
                "spool_mb": args.ingress_spool_mb,
            }
            if (args.listen_udp is not None
                or args.listen_tcp is not None)
            else None
        ),
    }
    # each distinct checkpoint path loads and compiles ONCE; tenants
    # sharing a path receive the SAME served-model object, which is
    # what makes the daemon share their predictor + compiled programs
    served_by_path = {}

    def _served(path):
        if path not in served_by_path:
            model, _labels, out_cols = _serving_form(
                load_model(path), args.label_index_col, args.fuse
            )
            served_by_path[path] = (model, out_cols)
        return served_by_path[path]

    specs = []
    for entry in entries:
        e = dict(entry)
        path = e.get("model")
        if not isinstance(path, str):
            raise SystemExit(
                f"tenant {e.get('id')!r}: 'model' must be a checkpoint "
                "path"
            )
        model, out_cols = _served(path)
        e["model"] = model
        e.setdefault("out_columns", out_cols)
        policy = e.get("row_policy", None if args.row_policy == "strict"
                       else args.row_policy)
        if policy is not None and policy != "strict":
            from sntc_tpu.data import CICIDS2017_CONTRACT

            e["row_policy"] = policy
            e["schema_contract"] = CICIDS2017_CONTRACT.with_mode(policy)
        else:
            e.pop("row_policy", None)
        specs.append(TenantSpec.from_dict(e, defaults))
    return specs


def _local_tpu_chips() -> int:
    """TPU chips on this host as JAX sees them, asked of a short-lived
    child process (0 when the default backend is not a TPU).  The
    caller must stay off JAX: a parent that has opened a backend holds
    every chip its children need."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.devices(); print(d[0].platform, len(d))"],
        capture_output=True, text=True,
    )
    if proc.returncode != 0:
        raise SystemExit(
            "fleet-serve: JAX found no backend to serve on:\n"
            + proc.stderr[-2000:]
        )
    platform, count = proc.stdout.split()[-2:]
    return int(count) if platform == "tpu" else 0


def _one_chip_env(chip: int) -> dict:
    """Environment that shows a worker exactly one local TPU chip as a
    1x1x1 process of its own (the recipe of jax's multi-process tests)."""
    return {
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


def cmd_fleet_serve(args) -> int:
    """Elastic serve fleet (r19): ONE coordinator process supervising
    N worker processes, each a plain ServeDaemon over its assigned
    tenant slice.  Placement is consistent hashing over tenant ids
    with the DRR weights as costs; liveness is a filesystem
    lease + heartbeat; a worker whose lease expires is declared dead
    and its tenants migrate (drain -> ship the fsck-verifiable state
    tree -> resume) to the survivors — the SAME first-class migration
    path rebalancing and the controller's ``migrate`` rung use.
    SIGTERM/Ctrl-C raises the fleet drain marker and fans SIGTERM out
    to every worker.  See docs/RESILIENCE.md "Elastic serve fleet".

    Internally re-invoked with ``--fleet-worker-id`` for each worker
    child (same flags, one worker identity)."""
    import itertools
    import signal as _signal
    import subprocess

    from sntc_tpu.serve.fleet import FleetCoordinator, FleetWorker

    if args.fleet_worker_id:
        # ---- worker mode (spawned by the coordinator) ----
        specs = {s.tenant_id: s for s in _load_tenant_specs(args)}
        worker = FleetWorker(
            args.fleet_worker_id, args.root, specs,
            daemon_kwargs=dict(
                shape_buckets=args.shape_buckets,
                pipeline_depth=args.pipeline_depth,
                autotune=args.autotune,
                dead_letter_keep=args.dead_letter_keep,
                device_faults=args.device_faults,
                compile_budget_s=args.compile_budget_s or None,
                standby_root=args.standby_root,
                repl_barrier_every=args.repl_barrier_every,
            ),
            controller=args.controller,
        )
        status = worker.run(poll_interval=args.poll_interval)
        print(json.dumps({
            "worker": args.fleet_worker_id,
            "tenants": {
                tid: row["state"]
                for tid, row in status.get("tenants", {}).items()
            },
        }))
        return 0

    # ---- coordinator mode ----
    _obs_start(args)
    with open(args.tenants) as f:
        doc = json.load(f)
    entries = doc["tenants"] if isinstance(doc, dict) else doc
    if not entries:
        raise SystemExit(f"{args.tenants}: no tenants declared")

    class _PlacementSpec:
        """The coordinator needs placement facts only — it never
        loads a model checkpoint (the workers do)."""

        def __init__(self, entry):
            self.placement_cost = entry.get("placement_cost")
            self.weight = float(entry.get("weight",
                                          args.tenant_weight))
            self.pinned_worker = entry.get("pinned_worker")

    specs = {e["id"]: _PlacementSpec(e) for e in entries}
    worker_ids = (
        args.worker_ids.split(",") if args.worker_ids
        else [f"w{i}" for i in range(args.workers)]
    )
    procs = {}
    child_argv = [sys.executable, "-m", "sntc_tpu"] + sys.argv[1:]
    # a chip belongs to one process at a time, so each worker gets its
    # OWN chip (a worker that opened every chip would lock the others
    # out) and more workers than chips is an error at start.  The
    # coordinator itself never opens a backend — it would hold the
    # chips — so a short-lived child counts them.
    chips = 0 if args.platform else _local_tpu_chips()
    if chips and len(worker_ids) > chips:
        raise SystemExit(
            f"fleet-serve: {len(worker_ids)} workers but {chips} TPU "
            "chip(s) on this host; a chip serves one process"
        )
    chip_of = {}

    def _spawn(wid):
        env = None
        if chips:
            held = {
                c for w, c in chip_of.items() if procs[w].poll() is None
            }
            free = [c for c in range(chips) if c not in held]
            if not free:
                raise RuntimeError(
                    f"fleet-serve: no free chip for worker {wid!r} "
                    f"({chips} chip(s), all held by live workers)"
                )
            chip_of[wid] = free[0]
            env = dict(os.environ, **_one_chip_env(free[0]))
        procs[wid] = subprocess.Popen(
            child_argv + ["--fleet-worker-id", wid], env=env
        )

    fresh_ids = itertools.count(len(worker_ids))

    def _scale_out(reason):
        wid = f"w{next(fresh_ids)}"
        _spawn(wid)
        return wid

    coord = FleetCoordinator(
        args.root, worker_ids, specs,
        lease_ttl_s=args.lease_ttl, boot_grace_s=args.boot_grace,
        dead_grace_s=args.dead_grace,
        vnodes=args.vnodes, slack=args.slack,
        scale_out_hook=_scale_out,
        standby_root=args.standby_root,
    )
    stop = {"sig": None}

    def _term(signum, frame):
        stop["sig"] = signum

    for sig in (_signal.SIGTERM, _signal.SIGINT):
        try:
            _signal.signal(sig, _term)
        except ValueError:
            pass
    for wid in worker_ids:
        _spawn(wid)
    print(
        f"fleet-serve: coordinator over {len(worker_ids)} workers x "
        f"{len(specs)} tenants -> {args.root}; SIGTERM/Ctrl-C drains "
        "the fleet",
        file=sys.stderr,
    )
    try:
        while stop["sig"] is None:
            coord.tick()
            time.sleep(args.poll_interval)
    finally:
        # the fan-out: raise the fleet drain marker (the workers'
        # loops watch it), then SIGTERM every child and wait
        coord.drain_fleet(f"signal {stop['sig']}")
        for p in procs.values():
            if p.poll() is None:
                p.terminate()
        deadline = time.time() + args.drain_timeout
        for p in procs.values():
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
        coord.tick()
        coord.close()
        _obs_finish(args)
    print(json.dumps(coord.status()))
    return 0


def cmd_fsck(args) -> int:
    """The storage doctor (r17): walk a checkpoint root — or a whole
    serve-daemon tenant tree — verify every registered durable
    artifact (WAL logs + sealed compaction checkpoints, JSONL
    journals, flow-state snapshot seals, markers, model-checkpoint
    manifests), repair what is safe (torn JSONL tails truncate with a
    journaled repair record; tmp orphans sweep), quarantine corrupt
    blobs to ``.corrupt/``, and print one machine-readable JSON
    report.  Exit 0 when the tree is (now) clean, 1 when unrepairable
    damage remains.  See docs/RESILIENCE.md "Durable storage
    lifecycle"."""
    from sntc_tpu.resilience.storage import fsck

    if args.fleet_root:
        from sntc_tpu.serve.fleet import fsck_fleet

        report = fsck_fleet(args.root, repair=not args.no_repair)
    else:
        report = fsck(
            args.root,
            repair=not args.no_repair,
            tenant_tree=args.tenant_tree,
        )
    if args.standby:
        # anti-entropy (r23): cross-verify every tenant replica under
        # the standby root against its sealed manifest AND against the
        # primary tree under ROOT; each mismatch journals a
        # replica_diverged and fails the exit code
        from sntc_tpu.resilience.replicate import fsck_standby

        standby_report = fsck_standby(
            args.standby,
            primary_root=args.root,
            repair=not args.no_repair,
        )
        report["standby"] = standby_report
        report["ok"] = report["ok"] and standby_report["ok"]
    if args.compile_cache or args.compile_cache_dir:
        # the persistent XLA compilation cache (r18): quarantine
        # unreadable/zero-length entries to .corrupt/ so serving
        # RECOMPILES a clean miss instead of crashing on a torn
        # executable; rides the same report + exit-code contract
        from sntc_tpu.utils.compile_cache import fsck_compile_cache

        cache_report = fsck_compile_cache(
            args.compile_cache_dir, repair=not args.no_repair,
        )
        report["compile_cache"] = cache_report
        report["ok"] = report["ok"] and cache_report["ok"]
    text = json.dumps(report, indent=1)
    if args.report:
        with open(args.report, "w") as f:
            f.write(text + "\n")
    print(text)
    return 0 if report["ok"] else 1


def cmd_fleet_restore_retired(args) -> int:
    """Recover a retired dead-source tenant tree (r23): fsck-verify
    ``<root>/fleet/retired/<name>`` and copy it into an explicit
    destination directory with a sealed restore manifest — never back
    into the serving namespace.  With no NAME, list what is
    restorable.  Exit 1 when the tree fails verification."""
    from sntc_tpu.serve.fleet import (
        RETIRED_DIR,
        fleet_meta_dir,
        restore_retired,
    )

    rdir = os.path.join(fleet_meta_dir(args.root), RETIRED_DIR)
    if not args.name:
        names = sorted(
            d for d in (os.listdir(rdir) if os.path.isdir(rdir) else [])
            if not d.startswith(".")
        )
        print(json.dumps({"root": args.root, "retired": names}))
        return 0
    if not args.dest:
        raise SystemExit("--dest is required to restore a tree")
    report = restore_retired(
        args.root, args.name, args.dest, repair=not args.no_repair,
    )
    print(json.dumps(report, indent=1))
    return 0 if report["ok"] else 1


def cmd_synth(args) -> int:
    from sntc_tpu.data import write_day_csvs

    paths = write_day_csvs(
        args.out, n_rows_per_day=args.rows // args.days, n_days=args.days,
        seed=args.seed,
    )
    print(json.dumps({"files": paths}))
    return 0


def add_platform_arg(parser) -> None:
    """The shared ``--platform`` CLI argument."""
    parser.add_argument(
        "--platform", default=None,
        help="force a JAX platform (e.g. 'cpu'); default is JAX's own "
        "default backend — a missing accelerator is an error there, "
        "never a CPU run under a device's name",
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sntc_tpu",
        description=__doc__.split("\n\n")[1],
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    def common(p):
        p.add_argument("--data", required=True,
                       help="directory of CICIDS2017-schema day CSVs")
        p.add_argument("--label-col", default="Label")
        p.add_argument("--binary", action="store_true",
                       help="benign-vs-attack relabel (config 1 [B:7])")
        p.add_argument("--metric", default="macroF1")
        p.add_argument("--seed", type=int, default=0)
        add_platform_arg(p)

    p = sub.add_parser("train", help="fit a pipeline, report held-out metric")
    common(p)
    p.add_argument("--estimator", default="mlp", choices=["lr", "mlp", "rf", "gbt", "dt", "nb", "svc"])
    p.add_argument("--model-out", default=None)
    p.add_argument("--test-fraction", type=float, default=0.2)
    p.add_argument("--max-iter", type=int, default=100)
    p.add_argument("--reg-param", type=float, default=1e-4)
    p.add_argument("--layers", default=TRAIN_DEFAULT_LAYERS)
    p.add_argument("--num-trees", type=int, default=20)
    p.add_argument("--max-depth", type=int, default=5)
    p.add_argument("--step-size", type=float, default=0.1)
    p.add_argument("--max-bins", type=int, default=128)
    p.add_argument("--chisq-top", type=int, default=0,
                   help="if > 0, use ChiSqSelector(k) instead of the scaler")
    p.add_argument("--features-col", default="features")
    _add_obs_flags(p)
    p.set_defaults(fn=cmd_train)

    p = sub.add_parser("evaluate", help="evaluate a saved model on CSVs")
    common(p)
    p.add_argument("--model", required=True)
    p.set_defaults(fn=cmd_evaluate)

    p = sub.add_parser("serve", help="micro-batch streaming inference [B:11]")
    p.add_argument("--model", required=True)
    p.add_argument("--watch", required=True, help="input CSV directory")
    p.add_argument("--out", required=True, help="output CSV directory")
    p.add_argument("--checkpoint", required=True,
                   help="offset/commit WAL directory (exactly-once resume)")
    p.add_argument("--label-index-col", default="label",
                   help="outputCol of the LABEL StringIndexer to strip "
                   "(feature-column indexers are kept)")
    p.add_argument("--max-files-per-batch", type=int, default=None)
    p.add_argument("--pipeline-depth", type=int, default=2,
                   help="in-flight micro-batches; > 1 also arms the "
                   "pipelined engine (overlapped sink delivery + source "
                   "prefetch); 1 = fully serial")
    p.add_argument("--shape-buckets", type=int, default=0,
                   help="pad micro-batches up to power-of-two row "
                   "buckets with this floor so the jitted predict "
                   "compiles once per bucket, not once per batch "
                   "shape; 0 = off")
    p.add_argument("--read-workers", type=int, default=4,
                   help="per-file read/parse pool width for multi-file "
                   "micro-batches (the ingest graph's parse-stage "
                   "workers; --autotune resizes it live)")
    p.add_argument("--autotune", action="store_true", dest="autotune",
                   default=False,
                   help="arm the ingest autotuner: resize "
                   "--read-workers / --prefetch-batches / "
                   "--pipeline-depth live from observed stage "
                   "latencies (hysteresis-guarded; every decision "
                   "journaled as autotune_decision events and "
                   "sntc_ingest_* metrics)")
    p.add_argument("--no-autotune", action="store_false", dest="autotune",
                   help="keep the ingest pools at their flag values")
    p.add_argument("--prefetch-batches", type=int, default=2,
                   help="background source reads staged ahead of the "
                   "engine (pipelined mode only); 0 = off")
    p.add_argument("--fuse", action="store_true", dest="fuse", default=True,
                   help="compile the serving pipeline with the whole-"
                   "pipeline fusion compiler: fold the scaler into the "
                   "model and jit each fusible stage run into ONE device "
                   "program (default)")
    p.add_argument("--no-fuse", action="store_false", dest="fuse",
                   help="serve the staged pipeline unfused (stage-by-"
                   "stage transforms; debugging/verification)")
    p.add_argument("--serve-kernels", default=None,
                   choices=["auto", "pallas", "interpret", "off"],
                   help="serving kernel tier (r21): hand-written Pallas "
                   "kernels for the fused hot path behind per-kernel "
                   "fit-guards — auto (pallas on TPU, off elsewhere), "
                   "pallas, interpret (CPU debugging twin), or off "
                   "(pure XLA).  Sets SNTC_SERVE_KERNELS before the "
                   "serving pipeline compiles; unset leaves the "
                   "environment's value in force")
    p.add_argument("--poll-interval", type=float, default=1.0)
    p.add_argument("--once", action="store_true",
                   help="drain available files and exit")
    p.add_argument("--health-json", default=None, metavar="PATH",
                   help="atomically rewrite a health/breaker/engine "
                   "status dump here every engine tick")
    p.add_argument("--max-pending-batches", type=int, default=None,
                   help="load-shed when the source backlog exceeds this "
                   "many micro-batches (default: never shed)")
    p.add_argument("--shed-policy", default="oldest",
                   choices=["oldest", "sample"],
                   help="shed the oldest surplus offsets, or process the "
                   "whole backlog row-subsampled (journaled either way)")
    p.add_argument("--max-batch-wall-time", type=float, default=None,
                   metavar="S", help="watchdog: flag a batch running "
                   "longer than this as UNHEALTHY (watchdog_stall event)")
    p.add_argument("--slo-p99-ms", type=float, default=None,
                   help="declared p99 batch-latency SLO: arms the "
                   "closed-loop controller, which steers the serving "
                   "knobs (pipeline depth, shape-bucket floor, shed, "
                   "ingest pools) toward it with hysteresis-guarded "
                   "journaled decisions; 0/unset = undeclared")
    p.add_argument("--slo-min-rows-per-sec", type=float, default=None,
                   help="declared throughput-floor SLO (binds while "
                   "the source has backlog); arms the controller "
                   "like --slo-p99-ms; 0/unset = undeclared")
    p.add_argument("--slo-max-shed-rate", type=float, default=None,
                   help="declared bound on the per-window fraction of "
                   "offsets load shedding may drop; arms the "
                   "controller; 0/unset = undeclared")
    p.add_argument("--controller", action="store_true",
                   dest="controller", default=True,
                   help="allow the closed-loop SLO controller (armed "
                   "by any --slo-* flag; decisions journaled to "
                   "<checkpoint>/controller.jsonl) — default")
    p.add_argument("--no-controller", action="store_false",
                   dest="controller",
                   help="keep every serving knob at its flag value "
                   "even when SLOs are declared")
    p.add_argument("--row-policy", default="strict",
                   choices=["strict", "salvage", "permissive"],
                   help="data-plane admission against the canonical "
                   "CICIDS2017 contract: strict = a poison batch fails "
                   "whole (today's behavior); salvage = poison ROWS are "
                   "excised to the row dead-letter and clean rows keep "
                   "serving; permissive = coerce what's coercible "
                   "(numeric strings, non-finite -> 0), then salvage")
    p.add_argument("--row-dead-letter", default=None, metavar="DIR",
                   help="row-level dead-letter directory (default: "
                   "<checkpoint>/dead_letter_rows): one JSONL per "
                   "batch with file/line/raw text/reason per excised "
                   "row")
    p.add_argument("--partial-fit", action="store_true",
                   help="incrementally refit a candidate head (LR/NB "
                   "sufficient-statistic partial_fit) from live "
                   "labeled batches and shadow it for promotion")
    p.add_argument("--drift-window", type=int, default=0, metavar="N",
                   help="arm the drift monitor: Jensen-Shannon "
                   "divergence of the last N committed batches' "
                   "prediction-mix/score histograms against the first "
                   "N (drift_detected event + model DEGRADED on "
                   "breach); 0 = off")
    p.add_argument("--drift-threshold", type=float, default=0.25,
                   help="divergence breach level for --drift-window")
    p.add_argument("--promote-from", default=None, metavar="DIR",
                   help="candidate model checkpoint to shadow-score on "
                   "live batches; promoted (atomic publish over "
                   "--model, incumbent retained at .prev, "
                   "between-batches hot-swap) when its macro-F1 beats "
                   "the incumbent over --shadow-window batches")
    p.add_argument("--shadow-window", type=int, default=8, metavar="N",
                   help="labeled batches the promotion gate averages "
                   "macro-F1 over")
    p.add_argument("--promote-margin", type=float, default=0.05,
                   help="macro-F1 lead the candidate must hold over "
                   "the incumbent to promote; with --partial-fit the "
                   "candidate is a refit of the incumbent, so refit "
                   "jitter re-promotes every window at margin 0")
    p.add_argument("--wal-mode", default="files",
                   choices=["files", "append"],
                   help="WAL format under --checkpoint: 'files' (one "
                   "json per intent/commit) or 'append' (one flushed "
                   "JSONL log per side — the high-throughput WAL, "
                   "compacted per --wal-compact-every)")
    p.add_argument("--wal-compact-every", type=int, default=256,
                   metavar="N",
                   help="append-WAL compaction interval in commits: "
                   "seal a wal_checkpoint.json and truncate the logs "
                   "every N commits (replay = checkpoint + tail); "
                   "0 = never compact")
    p.add_argument("--wal-keep-commits", type=int, default=64,
                   metavar="N",
                   help="files-WAL retention: committed intent/commit "
                   "pairs older than the last N are pruned; 0 = keep "
                   "forever")
    p.add_argument("--dead-letter-keep", type=int, default=200,
                   metavar="N",
                   help="dead-letter retention: keep the newest N "
                   "evidence files per dead-letter dir, drop the "
                   "oldest with a counted dead_letter_dropped; "
                   "0 = unbounded")
    p.add_argument("--disk-budget-mb", type=float, default=None,
                   metavar="MB",
                   help="byte budget for the checkpoint root: usage "
                   "is measured into sntc_disk_* gauges each tick and "
                   "a breach emits disk_budget_exceeded (DEGRADED "
                   "health); unset = measure only")
    p.add_argument("--batch-retry-attempts", type=int, default=2,
                   help="in-place attempts per read/sink stage before a "
                   "round counts as failed (1 = no retry)")
    p.add_argument("--max-batch-failures", type=int, default=3,
                   help="failed rounds before a poison batch is "
                   "dead-lettered and committed; 0 = first failure "
                   "kills the query (pre-r6 semantics)")
    p.add_argument("--from-capture", default=None,
                   choices=["pcap", "netflow"],
                   help="serve RAW captures: --watch holds pcap/.nf5 "
                   "capture files and a stateful keyed-window operator "
                   "computes the CICIDS2017 flow features live "
                   "(crash-safe state under <checkpoint>/flow_state); "
                   "unset = the default precomputed-CSV mode")
    p.add_argument("--flow-timeout", type=float, default=120.0,
                   metavar="S",
                   help="session-window quiet gap: a flow idle longer "
                   "than this (behind the watermark) is COMPLETE and "
                   "its feature row emits (CICFlowMeter's flow "
                   "timeout)")
    p.add_argument("--flow-activity-timeout", type=float, default=5.0,
                   metavar="S",
                   help="Active/Idle split gap inside a flow window "
                   "(CICFlowMeter's activity timeout; pcap only)")
    p.add_argument("--flow-lateness", type=float, default=5.0,
                   metavar="S",
                   help="allowed event-time lateness: the watermark "
                   "trails the max seen timestamp by this much; "
                   "records behind the watermark drop with reason "
                   "late_record (journaled, counted)")
    p.add_argument("--flow-max-packets", type=int, default=500_000,
                   help="hard cap on buffered records across all open "
                   "windows: beyond it the oldest flows force-evict "
                   "early (reason state_cap) so operator state stays "
                   "bounded under any replay")
    p.add_argument("--device-faults", action="store_true",
                   dest="device_faults", default=True,
                   help="arm the compute-plane fault domain: classify "
                   "device/XLA errors (OOM / compile / device lost) "
                   "and respond per kind — OOM-adaptive batch "
                   "splitting, per-signature compile poisoning with "
                   "host fallback, HOST_DEGRADED with probe-gated "
                   "recovery (default)")
    p.add_argument("--no-device-faults", action="store_false",
                   dest="device_faults",
                   help="pre-r18 behavior: device errors raise through "
                   "the generic retry/quarantine machinery")
    p.add_argument("--compile-budget-s", type=float, default=30.0,
                   metavar="S",
                   help="per-signature compile wall-time watchdog: a "
                   "fused-program compile exceeding this poisons that "
                   "(segment, signature) and serves it through the "
                   "eager host fallback; 0 = unarmed")
    p.add_argument("--listen-udp", type=int, default=None, metavar="PORT",
                   help="live network front door: bind a supervised "
                   "UDP listener for NetFlow v5 datagrams; --watch "
                   "becomes the ingress SPOOL the listener seals "
                   "replayable capture files into (0 = ephemeral "
                   "port, published in <watch>/ingress_stats.json); "
                   "loss is counted, never silent — see "
                   "docs/RESILIENCE.md 'Network ingress'")
    p.add_argument("--listen-tcp", type=int, default=None, metavar="PORT",
                   help="live network front door: bind a framed TCP "
                   "row listener (4-byte big-endian length + one CSV "
                   "row per frame); --watch becomes the ingress "
                   "spool; torn frames quarantine, over-budget spool "
                   "pauses reads (sender backpressure)")
    p.add_argument("--ingress-spool-mb", type=float, default=None,
                   metavar="MB",
                   help="ingress spool byte budget: TCP pauses reads "
                   "over it, UDP sheds at ingress (counted "
                   "spool_over_budget) after a committed-file prune "
                   "— bounded disk instead of ENOSPC death; unset = "
                   "unbudgeted")
    p.add_argument("--standby-root", default=None, metavar="DIR",
                   help="warm-standby disaster recovery (r23): "
                   "continuously replicate the checkpoint's durable "
                   "artifact tree (+ the sink) to <DIR>/default/ with "
                   "sealed manifests and commit barriers, so a lost "
                   "primary disk promotes from the replica with "
                   "measured RPO/RTO — see docs/RESILIENCE.md "
                   "'Disaster recovery'; unset = no replication")
    p.add_argument("--repl-barrier-every", type=int, default=1,
                   metavar="N",
                   help="seal a replication commit barrier every N "
                   "engine commits (ReplicationPlane barrier_every): "
                   "1 = every commit (tightest RPO), larger trades "
                   "barrier lag for ship amortization")
    _add_obs_flags(p)
    add_platform_arg(p)
    p.set_defaults(fn=cmd_serve)

    # flags shared by serve-daemon and fleet-serve (the fleet workers
    # are plain serve daemons, so the whole daemon surface forwards)
    p = daemon_flags = argparse.ArgumentParser(add_help=False)
    p.add_argument("--tenants", required=True, metavar="JSON",
                   help="tenant spec file: {\"tenants\": [{\"id\", "
                   "\"model\", \"watch\", \"out\", ...per-tenant "
                   "overrides}]}")
    p.add_argument("--root", required=True,
                   help="daemon root: per-tenant checkpoints/WALs/"
                   "dead-letters land under <root>/tenant/<id>/")
    p.add_argument("--label-index-col", default="label")
    p.add_argument("--max-files-per-batch", type=int, default=1,
                   help="micro-batch size in source files, per tenant "
                   "(TenantSpec max_batch_offsets)")
    p.add_argument("--pipeline-depth", type=int, default=1,
                   help="per-tenant in-flight micro-batches; > 1 arms "
                   "each tenant's overlapped sink delivery")
    p.add_argument("--shape-buckets", type=int, default=0,
                   help="power-of-two row bucketing for the SHARED "
                   "predictors (compile once per bucket across all "
                   "tenants of a pipeline); 0 = off")
    p.add_argument("--fuse", action="store_true", dest="fuse",
                   default=True,
                   help="compile each distinct tenant pipeline with the "
                   "whole-pipeline fusion compiler (default)")
    p.add_argument("--no-fuse", action="store_false", dest="fuse")
    p.add_argument("--autotune", action="store_true", dest="autotune",
                   default=False,
                   help="arm per-tenant ingest autotuners drawing from "
                   "ONE shared tuning budget (total extra parse "
                   "threads / staged ranges / pipeline slots capped "
                   "across the fleet)")
    p.add_argument("--no-autotune", action="store_false",
                   dest="autotune")
    p.add_argument("--tenant-weight", type=float, default=1.0,
                   help="default fair-share weight (TenantSpec weight): "
                   "deficit round-robin credits per scheduling round")
    p.add_argument("--max-rows-per-sec", type=float, default=None,
                   help="default per-tenant admission rate quota "
                   "(TenantSpec max_rows_per_sec): a token bucket "
                   "charged at commit throttles a flooding tenant at "
                   "its own edge; unset = unlimited")
    p.add_argument("--max-pending-batches", type=int, default=None,
                   help="default per-tenant backlog cap (TenantSpec "
                   "max_pending_batches): surplus is shed through the "
                   "tenant's own journaled shed path")
    p.add_argument("--shed-policy", default="oldest",
                   choices=["oldest", "sample"],
                   help="default per-tenant shed policy (TenantSpec "
                   "shed_policy)")
    p.add_argument("--quarantine-after", type=int, default=3,
                   help="unhealthy strikes (quarantine/retry_exhausted/"
                   "breaker_open events tagged with the tenant) before "
                   "the tenant is QUARANTINED (TenantSpec "
                   "quarantine_after)")
    p.add_argument("--quarantine-cooldown", type=float, default=30.0,
                   metavar="S",
                   help="seconds a QUARANTINED tenant holds before "
                   "probation back to OK (TenantSpec "
                   "quarantine_cooldown_s)")
    p.add_argument("--stop-after", type=int, default=3,
                   help="quarantine episodes before the tenant is "
                   "STOPPED and its breakers evicted (TenantSpec "
                   "stop_after)")
    p.add_argument("--row-policy", default="strict",
                   choices=["strict", "salvage", "permissive"],
                   help="default per-tenant data-plane admission "
                   "(TenantSpec row_policy) against the canonical "
                   "CICIDS2017 contract")
    p.add_argument("--from-capture", default=None,
                   choices=["pcap", "netflow"],
                   help="default per-tenant raw-capture mode "
                   "(TenantSpec from_capture): tenants' watch dirs "
                   "hold capture files and each tenant runs its own "
                   "stateful flow-window operator (state under "
                   "tenant/<id>/ckpt/flow_state); per-tenant "
                   "'flow_options' in the tenants JSON tunes the "
                   "window knobs")
    p.add_argument("--slo-p99-ms", type=float, default=None,
                   help="default per-tenant p99 latency SLO "
                   "(TenantSpec slo_p99_ms; per-tenant JSON "
                   "overrides); the --controller setpoint; "
                   "0/unset = undeclared")
    p.add_argument("--slo-min-rows-per-sec", type=float, default=None,
                   help="default per-tenant throughput-floor SLO "
                   "(TenantSpec slo_min_rows_per_sec); 0/unset = "
                   "undeclared")
    p.add_argument("--slo-max-shed-rate", type=float, default=None,
                   help="default per-tenant shed-rate SLO bound "
                   "(TenantSpec slo_max_shed_rate, a fraction in "
                   "(0, 1]); 0/unset = undeclared")
    p.add_argument("--controller", action="store_true",
                   dest="controller", default=False,
                   help="arm the closed-loop SLO controller: one "
                   "guarded knob step per window toward the declared "
                   "per-tenant SLOs (protect compliant tenants, "
                   "degrade the violator throttle->shed->escalate), "
                   "owning the per-tenant ingest tuners; decisions "
                   "journaled to <root>/controller.jsonl")
    p.add_argument("--no-controller", action="store_false",
                   dest="controller",
                   help="keep every serving knob at its flag value")
    p.add_argument("--batch-retry-attempts", type=int, default=2)
    p.add_argument("--max-batch-failures", type=int, default=3,
                   help="default per-tenant poison-batch threshold "
                   "(TenantSpec max_batch_failures); 0 = first failure "
                   "surfaces (and strikes the tenant)")
    p.add_argument("--disk-budget-mb", type=float, default=None,
                   metavar="MB",
                   help="default per-tenant disk byte budget "
                   "(TenantSpec disk_budget_mb): the tenant/<id>/ "
                   "subtree is measured into sntc_disk_bytes{tenant=} "
                   "each round and a breach degrades THAT tenant's "
                   "health; 0/unset = measure only")
    p.add_argument("--root-disk-budget-mb", type=float, default=None,
                   metavar="MB",
                   help="global disk byte budget for the whole daemon "
                   "root (all tenants + shared journals)")
    p.add_argument("--dead-letter-keep", type=int, default=200,
                   metavar="N",
                   help="per-tenant dead-letter retention: keep the "
                   "newest N evidence files per dead-letter dir "
                   "(counted dead_letter_dropped); 0 = unbounded")
    p.add_argument("--device-faults", action="store_true",
                   dest="device_faults", default=True,
                   help="arm ONE compute-plane fault domain shared by "
                   "every tenant's predictor (tenants share the "
                   "physical device): device/XLA errors respond per "
                   "kind and never strike a tenant's ladder (default)")
    p.add_argument("--no-device-faults", action="store_false",
                   dest="device_faults",
                   help="pre-r18 behavior: device errors ride the "
                   "generic per-tenant retry/quarantine machinery")
    p.add_argument("--compile-budget-s", type=float, default=30.0,
                   metavar="S",
                   help="per-signature compile wall-time watchdog for "
                   "the shared predictors (see serve --compile-"
                   "budget-s); 0 = unarmed")
    p.add_argument("--poll-interval", type=float, default=1.0)
    p.add_argument("--once", action="store_true",
                   help="drain available files across all tenants and "
                   "exit")
    p.add_argument("--health-json", default=None, metavar="PATH",
                   help="atomically rewrite the daemon status dump "
                   "(per-tenant states, compile ledger, health, "
                   "breakers) here every scheduling round")
    p.add_argument("--listen-udp", type=int, default=None, metavar="PORT",
                   help="default per-tenant UDP ingress (TenantSpec "
                   "ingress): each tenant's watch dir becomes its own "
                   "ingress spool behind a supervised NetFlow v5 "
                   "listener — use 0 (ephemeral, published in "
                   "<watch>/ingress_stats.json) so tenants never "
                   "collide on a port; per-tenant 'ingress' JSON "
                   "blocks override")
    p.add_argument("--listen-tcp", type=int, default=None, metavar="PORT",
                   help="default per-tenant framed-TCP row ingress "
                   "(TenantSpec ingress); 0 = ephemeral per tenant, "
                   "published in the tenant's ingress_stats.json")
    p.add_argument("--ingress-spool-mb", type=float, default=None,
                   metavar="MB",
                   help="default per-tenant ingress spool byte budget "
                   "(TenantSpec ingress spool_mb): over it TCP pauses "
                   "reads and UDP sheds at ingress, counted — never "
                   "ENOSPC death")
    p.add_argument("--standby-root", default=None, metavar="DIR",
                   help="warm-standby disaster recovery (r23): every "
                   "tenant's durable tree (+ sink) replicates to "
                   "<DIR>/<tenant>/ with sealed manifests and commit "
                   "barriers; a fleet coordinator also prefers "
                   "replica-restore when a dead worker's primary tree "
                   "cannot ship — see docs/RESILIENCE.md 'Disaster "
                   "recovery'")
    p.add_argument("--repl-barrier-every", type=int, default=1,
                   metavar="N",
                   help="seal a replication commit barrier every N "
                   "commits per tenant (ReplicationPlane "
                   "barrier_every); 1 = tightest RPO")
    _add_obs_flags(p)
    add_platform_arg(p)

    p = sub.add_parser(
        "serve-daemon",
        parents=[daemon_flags],
        help="multi-tenant streaming inference: N tenant streams, one "
        "shared device program cache, fair scheduling, per-tenant "
        "isolation (docs/RESILIENCE.md)",
    )
    p.set_defaults(fn=cmd_serve_daemon)

    p = sub.add_parser(
        "fleet-serve",
        parents=[daemon_flags],
        help="elastic serve fleet: one coordinator process supervising "
        "N serve-daemon workers with leases, consistent-hash "
        "placement, worker-death recovery, and first-class tenant "
        "migration (docs/RESILIENCE.md)",
    )
    p.add_argument("--workers", type=int, default=2, metavar="N",
                   help="worker processes to spawn (ids w0..wN-1); "
                   "each runs a plain ServeDaemon over its assigned "
                   "tenant slice under <root>/worker/<id>/")
    p.add_argument("--worker-ids", default=None, metavar="IDS",
                   help="explicit comma-separated worker ids "
                   "(overrides --workers; the ids TenantSpec "
                   "pinned_worker entries must name)")
    p.add_argument("--lease-ttl", type=float, default=5.0, metavar="S",
                   help="worker lease TTL (FleetCoordinator "
                   "lease_ttl_s): a worker whose heartbeat marker is "
                   "older is declared DEAD and its tenants migrate to "
                   "the survivors")
    p.add_argument("--boot-grace", type=float, default=30.0,
                   metavar="S",
                   help="first-heartbeat grace (FleetCoordinator "
                   "boot_grace_s): how long a spawned worker may take "
                   "to come up before it counts as dead")
    p.add_argument("--dead-grace", type=float, default=None,
                   metavar="S",
                   help="ship fence (FleetCoordinator dead_grace_s): "
                   "a dead worker's tenant trees only ship after its "
                   "lease stays expired this much LONGER, with a final "
                   "lease re-read — a slow-but-alive worker gets the "
                   "window to renew (default: 2 x lease TTL)")
    p.add_argument("--vnodes", type=int, default=64,
                   help="virtual nodes per worker on the consistent-"
                   "hash ring (FleetCoordinator vnodes)")
    p.add_argument("--slack", type=float, default=1.25,
                   help="bounded-load placement slack (FleetCoordinator "
                   "slack): per-worker capacity = slack x total "
                   "placement cost / workers")
    p.add_argument("--drain-timeout", type=float, default=60.0,
                   metavar="S",
                   help="seconds to wait for workers to settle after "
                   "the SIGTERM fan-out before killing them")
    p.add_argument("--fleet-worker-id", default=None,
                   help="internal: run as the named fleet WORKER "
                   "instead of the coordinator (the coordinator "
                   "re-invokes itself with this flag per worker)")
    p.set_defaults(fn=cmd_fleet_serve)

    p = sub.add_parser(
        "fsck",
        help="verify + repair every durable artifact under a "
        "checkpoint root (WAL seals/tails, journals, flow-state "
        "snapshots, markers, model manifests); machine-readable "
        "report; exit 1 when unrepairable damage remains",
    )
    p.add_argument("root", help="checkpoint root to doctor (a serve "
                   "--checkpoint dir, or a serve-daemon --root with "
                   "--tenant-tree)")
    p.add_argument("--tenant-tree", action="store_true",
                   help="also walk every <root>/tenant/<id>/ckpt "
                   "(the serve-daemon layout)")
    p.add_argument("--fleet-root", action="store_true",
                   help="treat ROOT as an elastic-fleet coordinator "
                   "root: doctor the fleet metadata (assignment "
                   "marker + journal, leases, request journals, "
                   "sealed migration manifests, torn mid-ship "
                   "copies) plus every <root>/worker/<id>/ daemon "
                   "tree; an unrepairable migration manifest exits 1")
    p.add_argument("--no-repair", action="store_true",
                   help="report only: no truncations, no quarantines, "
                   "no tmp sweeps")
    p.add_argument("--compile-cache", action="store_true",
                   help="also doctor the persistent XLA compilation "
                   "cache (the dir enable_persistent_cache manages, "
                   "from JAX_COMPILATION_CACHE_DIR / the default "
                   "base): zero-length/unreadable entries quarantine "
                   "to .corrupt/ so serving recompiles instead of "
                   "crashing; tmp orphans sweep")
    p.add_argument("--compile-cache-dir", default=None, metavar="DIR",
                   help="explicit compilation-cache directory to "
                   "doctor (implies --compile-cache)")
    p.add_argument("--standby", default=None, metavar="DIR",
                   help="anti-entropy (r23): also cross-verify every "
                   "tenant replica under this warm-standby root — "
                   "sealed manifest, replica content hashes, and "
                   "primary-vs-replica for files both sides hold; "
                   "each divergence journals replica_diverged and "
                   "exits 1 (with repair, the diverged replica copy "
                   "quarantines so the next ship re-seeds it)")
    p.add_argument("--report", default=None, metavar="PATH",
                   help="also write the JSON report here")
    add_platform_arg(p)
    p.set_defaults(fn=cmd_fsck)

    p = sub.add_parser(
        "fleet-restore-retired",
        help="recover a retired dead-source tenant tree "
        "(fleet/retired/<tid>.<wid>.<epoch>): fsck-verify and copy it "
        "into an explicit --dest with a sealed restore manifest; no "
        "NAME lists what is restorable",
    )
    p.add_argument("root", help="fleet coordinator root")
    p.add_argument("name", nargs="?", default=None,
                   help="retired tree name (<tid>.<wid>.<epoch>); "
                   "omit to list")
    p.add_argument("--dest", default=None, metavar="DIR",
                   help="destination directory for the verified copy "
                   "(required with NAME; never the serving namespace)")
    p.add_argument("--no-repair", action="store_true",
                   help="verify only: no torn-tail truncations inside "
                   "the retired tree")
    add_platform_arg(p)
    p.set_defaults(fn=cmd_fleet_restore_retired)

    p = sub.add_parser("synth", help="write schema-identical synthetic day CSVs")
    p.add_argument("--out", required=True)
    p.add_argument("--rows", type=int, default=80_000)
    p.add_argument("--days", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    p.set_defaults(fn=cmd_synth)

    args = ap.parse_args(argv)
    if getattr(args, "platform", None):
        import jax

        jax.config.update("jax_platforms", args.platform)
    # Spark pays no per-process compile; neither should a CLI user on
    # their second run (SURVEY.md §3.5 cold-start — docs/PARITY.md)
    from sntc_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    # every CLI gets the metrics plane: the event→metrics bridge folds
    # whatever the command emits (engines install it themselves, but
    # train/evaluate emit too — CV retries, checkpoint fallbacks)
    from sntc_tpu.obs import install_event_metrics

    install_event_metrics()
    return args.fn(args)


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
