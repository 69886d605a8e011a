"""Whole-pipeline fusion compiler — one device program per fusible run.

The serving hot path executed a fitted ``PipelineModel`` stage-by-stage:
every feature transformer round-tripped its output through a host numpy
column before the next stage ran — the ML-pipeline analog of the
per-operator interpretation Spark's whole-stage codegen eliminates
(SURVEY.md §2.6).  ``compile_pipeline`` compiles that interpretation
away:

1. **rewrite** — algebraic folds run first (``fuse.rules``: scaler →
   linear/MLP weight folding), shrinking the pipeline before fusion;
2. **partition** — the stage list splits into MAXIMAL runs of stages
   whose fitted instances export a pure device fn via the capability
   registry (``fuse.registry``); a classifier head with a packed device
   serve program terminates its run;
3. **compile** — each run becomes ONE :class:`FusedSegment`: a single
   jitted XLA program (per input signature; shape-bucketed serving keys
   it per bucket) with the host input columns as donated arguments, all
   intermediate columns living only in device registers/HBM, and ONE
   packed output per head.  Non-fusible stages (object/ragged columns,
   row-dropping ``handleInvalid='skip'``, data-dependent validation)
   stay eager between segments — the row-validity-mask contract of the
   shape-bucketed engine is untouched because row-dropping stages are
   never fused.  The ``VALID_COL`` mask column itself is never a plan
   read or write, so :class:`FusedSegment` carries it through verbatim
   (outputs layer onto the INPUT frame): bucket padding AND the r10
   admission layer's row salvage both compose with fusion — an excised
   row rides the fused program inside the batch's unchanged shape and
   is filtered only at the predictor's finalize, so ``compile_events``
   stays flat under salvage.

Evidence: every segment dispatch records its host→device uploads and
device→host materializations in the process transfer ledger
(``sntc_tpu.utils.profiling.transfer_ledger``); a fully-fused pipeline
serves each micro-batch with exactly ONE upload and ONE download
(journaled by bench config 6).

Scope notes: fused segments are a serving-time artifact — persist the
ORIGINAL fitted pipeline, not the compiled one.  Output frames omit
intermediate columns that exist only to feed a later fused stage
(pass ``keep=('col',)`` to retain one); every column a later eager
stage reads is kept automatically.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Iterable, List, Optional, Sequence, Tuple

import numpy as np

from sntc_tpu.core.base import PipelineModel, Transformer
from sntc_tpu.core.frame import Frame
from sntc_tpu.kernels import registry as kreg
from sntc_tpu.obs import cost as obs_cost
from sntc_tpu.feature.vector_assembler import VectorAssembler
from sntc_tpu.fuse.registry import (
    F32_CAST,
    F32_ONLY,
    F64,
    DevicePlan,
    device_plan_for,
)
from sntc_tpu.fuse.rules import fold_scalers
from sntc_tpu.models.base import ClassificationModel
from sntc_tpu.obs.metrics import inc
from sntc_tpu.obs.trace import span
from sntc_tpu.resilience.device import (
    DeviceExecError,
    classify_device_error,
)
from sntc_tpu.resilience.faults import fault_point
from sntc_tpu.utils.profiling import active_ledgers


def _fusible_head(stage) -> bool:
    return isinstance(stage, ClassificationModel) and stage.has_device_serve()


class FusedSegment(Transformer):
    """One maximal fusible run compiled into a single device program.

    ``transform_async`` uploads the segment's external input columns
    (cast per each plan's declared policy — identical to the casts the
    staged path applies), dispatches ONE jitted program computing every
    fused stage plus the optional head's packed serve output, and
    returns a finalize that materializes the outputs into a Frame.
    Falls back to the eager stage-by-stage transform for empty frames
    and dtype-preserving stages bound to non-float32 columns
    (``fallbacks`` counts them).  Programs are cached per input
    signature — ``compile_events`` mirrors the BatchPredictor shape
    ledger, so shape-bucketed serving keeps it flat after warmup.
    """

    def __init__(
        self,
        stages: Sequence[Transformer],
        plans: Sequence[DevicePlan],
        head: Optional[ClassificationModel] = None,
        keep: Iterable[str] = (),
    ):
        super().__init__()
        if len(stages) != len(plans):
            raise ValueError("one DevicePlan per fused stage required")
        self._stages = list(stages)
        self._plans = list(plans)
        self._head = head
        self._keep = frozenset(keep)
        self._programs: dict = {}
        self._lock = threading.Lock()
        self.compile_events = 0  # distinct input signatures compiled
        self.invocations = 0  # fused dispatches
        self.fallbacks = 0  # eager fallbacks (empty/dtype-gated)
        # compute-plane fault domain (r18): set by
        # attach_device_domain (via BatchPredictor).  A compile failure
        # or watchdog breach poisons exactly (this segment, that input
        # signature) — later binds of the signature take the eager
        # host path while every other signature keeps compiling on
        # device; HOST_DEGRADED diverts ALL binds eagerly.
        self._domain = None
        self.segment_index: Optional[int] = None  # position in the plan
        self._poisoned: dict = {}  # signature -> reason
        self.poisoned_served = 0  # binds served off a poisoned signature
        # SNTC_OBS_COST_ANALYSIS=1: XLA cost_analysis() per compiled
        # signature (flops / bytes accessed), keyed by signature repr —
        # the device-cost side of the obs span correlation (extraction
        # shared with bench via obs.cost since r21)
        self.cost_analyses: dict = {}
        # per-signature measured wall time under the same hook:
        # sig repr -> [seconds, invocations], the roofline numerator
        self.cost_timings: dict = {}
        # per-SEGMENT transfer counters: fusion_stats() aggregates these
        # per model, so one engine's evidence is never polluted by other
        # fused models in the process (the global ledger stays the
        # process-wide view)
        self.uploads = 0
        self.downloads = 0

        # external inputs: the first consuming plan's read policy decides
        # the upload cast (in-segment columns arrive as device values).
        # Two plans reading ONE external column under DIFFERENT policies
        # cannot share a segment — the upload cast of one would bypass
        # the other's dtype guard and break the bitwise contract; the
        # planner splits such runs, and this constructor enforces it.
        external: List[Tuple[str, str]] = []
        produced: set = set()
        policies: dict = {}
        for plan in self._plans:
            for r in plan.reads:
                if r in produced:
                    continue
                if r not in policies:
                    policies[r] = plan.read_policy
                    external.append((r, plan.read_policy))
                elif policies[r] != plan.read_policy:
                    raise ValueError(
                        f"conflicting read policies for column {r!r} "
                        f"({policies[r]} vs {plan.read_policy}): split "
                        "these stages into separate segments"
                    )
            produced.update(plan.writes)
        if head is not None:
            # the head input is cast to float32 IN-PROGRAM (mirroring the
            # staged ClassificationModel.transform astype), so any upload
            # policy on an external features column is compatible
            fc = head.getFeaturesCol()
            if fc not in produced and fc not in policies:
                external.append((fc, F32_CAST))
        self._external = external

        # liveness: a written column whose FINAL value is only consumed
        # inside the segment is dead — it never leaves the device.  Leaf
        # outputs, `keep` columns, and anything a later pipeline stage
        # reads (folded into `keep` by compile_pipeline) materialize.
        write_order: List[str] = []
        last_writer: dict = {}
        for i, plan in enumerate(self._plans):
            for w in plan.writes:
                if w in write_order:
                    write_order.remove(w)
                write_order.append(w)
                last_writer[w] = i
        head_reads = {head.getFeaturesCol()} if head is not None else set()
        self._live_writes = [
            w
            for w in write_order
            if w in self._keep
            or not (
                w in head_reads
                or any(
                    w in self._plans[j].reads
                    for j in range(last_writer[w] + 1, len(self._plans))
                )
            )
        ]

    # -- introspection ------------------------------------------------------

    @property
    def fused_stages(self) -> List[Transformer]:
        """The original fitted stages this segment compiled (head last)."""
        out = list(self._stages)
        if self._head is not None:
            out.append(self._head)
        return out

    def input_columns(self) -> List[str]:
        return [name for name, _ in self._external]

    def __repr__(self) -> str:
        names = ", ".join(type(s).__name__ for s in self.fused_stages)
        return f"FusedSegment[{names}]"

    # -- execution ----------------------------------------------------------

    def _bind(self, frame: Frame) -> Optional[List[np.ndarray]]:
        """Host arrays for the program arguments, cast per policy;
        None when a dtype-preserving plan sees a non-float32 column
        (the eager path keeps the exact host semantics)."""
        args: List[np.ndarray] = []
        for name, policy in self._external:
            col = frame[name]
            if not isinstance(col, np.ndarray):
                col = np.asarray(col)  # device-resident column: materialize
            if policy == F32_ONLY:
                if col.dtype != np.float32:
                    return None
                args.append(col)
            elif policy == F64:
                args.append(np.asarray(col, np.float64))
            else:  # F32_CAST — the cast every fused stage applies itself
                args.append(col.astype(np.float32, copy=False))
        return args

    @staticmethod
    def _place_args(args: List[np.ndarray]) -> list:
        """Serve-mesh row placement (r22): with a serve mesh armed
        (``parallel.context.get_serve_mesh``), the dispatched batch rows
        split over the ``"data"`` axis by ``NamedSharding`` before the
        program call — the fused programs are purely row-wise, so GSPMD
        runs each shard on its own device and the gathered outputs are
        bitwise identical to the 1-device program.  Batches whose rows
        do not divide the mesh (only possible below the bucket floor)
        dispatch single-device unchanged, and a consistent placement
        policy keeps ONE compiled program per (signature, placement)."""
        from sntc_tpu.parallel.context import get_serve_mesh

        mesh = get_serve_mesh()
        if mesh is None or not args:
            return args
        from sntc_tpu.parallel.mesh import DATA_AXIS, data_sharding

        size = int(mesh.shape.get(DATA_AXIS, 1))
        n = int(args[0].shape[0])
        if size <= 1 or n == 0 or n % size:
            return args
        import jax

        return [
            jax.device_put(a, data_sharding(mesh, a.ndim)) for a in args
        ]

    @staticmethod
    def _signature(args: List[np.ndarray]):
        import jax

        # donation frees the uploaded input buffers for reuse by the
        # program's outputs; on CPU the backend ignores donation (and the
        # host buffer may be aliased zero-copy), so gate it off there
        donate = jax.default_backend() != "cpu"
        return (
            tuple((a.shape, a.dtype.str) for a in args),
            donate,
        )

    def _program(self, args: List[np.ndarray], sig=None):
        if sig is None:
            sig = self._signature(args)
        with self._lock:
            prog = self._programs.get(sig)
            if prog is not None:
                return prog
        import jax

        donate = sig[1]
        names = [n for n, _ in self._external]
        plans, head, live = self._plans, self._head, self._live_writes

        def run(*xs):
            import jax.numpy as jnp

            env = dict(zip(names, xs))
            for plan in plans:
                env.update(plan.apply(env))
            outs = []
            if head is not None:
                # the staged path's ClassificationModel.transform casts
                # features to float32 before predicting — replicate it,
                # or an x64-produced f64 feature column would run the
                # head in f64 and diverge from the staged output
                x = env[head.getFeaturesCol()].astype(jnp.float32)
                outs.append(head._predict_all_dev(x))
            outs.extend(env[w] for w in live)
            return tuple(outs)

        prog = jax.jit(
            run,
            donate_argnums=tuple(range(len(names))) if donate else (),
        )
        with self._lock:
            fresh = sig not in self._programs
            if fresh:
                self._programs[sig] = prog
                self.compile_events += 1
            prog = self._programs[sig]
        if fresh:
            inc("sntc_fuse_compile_events_total")
            if obs_cost.enabled():
                # device-cost hook (opt-in — it compiles the program
                # eagerly): XLA's own FLOPs/bytes estimate for this
                # signature, correlatable with the host fuse.* spans
                # and fed to the MFU/roofline plane (obs.cost)
                self.cost_analyses[repr(sig[0])] = obs_cost.extract(
                    prog, args
                )
        return prog

    def _transform_eager(self, frame: Frame) -> Frame:
        out = frame
        for stage in self._stages:
            out = stage.transform(out)
        if self._head is not None:
            out = self._head.transform(out)
        return out

    def transform(self, frame: Frame) -> Frame:
        return self.transform_async(frame)()

    def _eager_async(self, frame: Frame, poisoned: bool = False):
        """One eager fallback serve (the shared bookkeeping for the
        empty/dtype gate, poisoned signatures, and HOST_DEGRADED)."""
        self.fallbacks += 1
        inc("sntc_fuse_fallbacks_total")
        if poisoned:
            with self._lock:
                self.poisoned_served += 1
        out = self._transform_eager(frame)
        return lambda: out

    def _poison(self, sig, reason: str, site: str) -> None:
        with self._lock:
            fresh = sig not in self._poisoned
            self._poisoned[sig] = reason
        if fresh and self._domain is not None:
            self._domain.note_poisoned(
                site=site, signature=repr(sig[0]), reason=reason,
                segment=self.segment_index,
            )

    def transform_async(self, frame: Frame):
        args = self._bind(frame) if frame.num_rows else None
        if args is None:
            return self._eager_async(frame)
        dom = self._domain
        if dom is not None and dom.host_degraded:
            dom.note_fallback()
            return self._eager_async(frame)
        sig = self._signature(args)
        if sig in self._poisoned:
            if dom is not None:
                dom.note_fallback(poisoned=True)
            return self._eager_async(frame, poisoned=True)
        fresh = sig not in self._programs
        budget = dom.policy.compile_budget_s if dom is not None else None
        # snapshot the ledgers to record into AT DISPATCH TIME: the
        # engine scopes its own (per-tenant) ledger on its thread, and
        # the finalize closure below may run on the delivery thread —
        # capturing here keeps attribution correct across threads
        ledgers = active_ledgers()
        args_dev = self._place_args(args)
        # kernels armed by THIS trace (none when its rows are sharded)
        kreg.begin_trace_capture(sharded=args_dev is not args)
        try:
            if fresh:
                # the DEVICE fault boundary for the fused-program
                # compile (chaos arms compile_error / kill here)
                fault_point("fuse.compile")
            t0 = time.perf_counter() if fresh else 0.0
            prog = self._program(args, sig)
            t_disp = (
                time.perf_counter() if obs_cost.enabled() else None
            )
            up_bytes = sum(a.nbytes for a in args)
            for led in ledgers:
                led.record_uploads(len(args), up_bytes)
            with span("fuse.dispatch", args=len(args)):
                # async dispatch; finalize materializes.  For a fresh
                # signature THIS call triggers the XLA compile, so the
                # wall time below is the watchdog's compile measurement.
                outs = prog(*args_dev)
            if fresh and budget is not None:
                elapsed = time.perf_counter() - t0
                if elapsed > budget:
                    # the compile finished but blew the budget: a
                    # signature this expensive to (re)compile is a
                    # serving hazard — poison it and serve the host
                    # path, exactly like a failed compile.  The
                    # just-compiled executable is EVICTED too: a
                    # poisoned signature never binds again, so keeping
                    # it would pin dead device memory for the process
                    # lifetime
                    with self._lock:
                        self._programs.pop(sig, None)
                    self._poison(
                        sig,
                        f"compile watchdog: {elapsed:.3f}s > "
                        f"budget {budget}s",
                        site="fuse.compile",
                    )
                    if dom is not None:
                        dom.note_fallback(poisoned=True)
                    return self._eager_async(frame, poisoned=True)
        except Exception as e:
            kind = classify_device_error(e)
            # the kernel-scope classifier widens to Pallas/Mosaic
            # lowering failures that are not XLA-runtime-shaped (e.g.
            # a compiled kernel on a CPU backend); it only matters when
            # this trace actually armed kernels — otherwise the strict
            # ladder below rules
            if (
                kreg.classify_kernel_error(e) == "compile_error"
                and kreg.traced_kernels()
            ):
                if kreg.serve_kernels_forced():
                    raise  # asked for by name: no twin behind its back
                kreg.poison_traced(repr(e))
                # a Pallas kernel INSIDE this fused trace failed to
                # compile: the segment itself is healthy, so poison
                # exactly those kernel signatures (done above), evict
                # the half-built program, and recompile the SAME fused
                # signature — the retrace sees the poisoned kernels
                # and lowers their jnp twins instead.  The batch serves
                # on the XLA path, not the eager host path, and no
                # fault reaches the domain's strike ladder.
                with self._lock:
                    self._programs.pop(sig, None)
                return self.transform_async(frame)
            if dom is not None and kind == "compile_error":
                # poison exactly (this segment, this signature); other
                # signatures keep compiling on device
                self._poison(sig, repr(e), site="fuse.compile")
                dom.note_fault(kind, site="fuse.compile")
                dom.note_fallback(poisoned=True)
                return self._eager_async(frame, poisoned=True)
            raise  # OOM / device_lost respond at the predictor layer
        with self._lock:
            self.invocations += 1
            self.uploads += len(args)
        head, live = self._head, self._live_writes
        if head is not None:
            inc("sntc_predict_head_dispatch_total", path="device")
        seg_index, sig_repr = self.segment_index, repr(sig[0])

        def finalize() -> Frame:
            try:
                with span("fuse.finalize"):
                    host = [np.asarray(o) for o in outs]
            except Exception as e:
                kind = classify_device_error(e)
                if kind is None:
                    raise
                # device-side materialization failure (overlap mode
                # surfaces these on the delivery thread): thread the
                # execution context — segment, signature — through the
                # error chain so the journaled evidence names the work
                # that died, not just the symptom (the engine adds the
                # batch id)
                raise DeviceExecError(
                    f"device {kind} while finalizing fused segment "
                    f"{seg_index} ({type(self).__name__}) signature "
                    f"{sig_repr}: {e}",
                    kind=kind, segment=seg_index, signature=sig_repr,
                ) from e
            down_bytes = sum(h.nbytes for h in host)
            for led in ledgers:
                led.record_downloads(len(host), down_bytes)
            with self._lock:
                self.downloads += len(host)
            if t_disp is not None:
                # dispatch -> host-materialized wall time: the roofline
                # numerator for this signature (obs.cost); gauges
                # update live so a scrape mid-serve sees current MFU
                dt = time.perf_counter() - t_disp
                with self._lock:
                    acc = self.cost_timings.setdefault(
                        sig_repr, [0.0, 0]
                    )
                    acc[0] += dt
                    acc[1] += 1
                    secs, inv = acc
                obs_cost.emit_mfu(
                    seg_index if seg_index is not None else 0,
                    obs_cost.roofline(
                        self.cost_analyses.get(sig_repr), secs, inv
                    ),
                )
            out_frame = frame
            feature_cols = host[1:] if head is not None else host
            for name, arr in zip(live, feature_cols):
                out_frame = out_frame.with_column(name, arr)
            if head is not None:
                packed = host[0]
                k = head.num_classes
                if head.getRawPredictionCol():
                    out_frame = out_frame.with_column(
                        head.getRawPredictionCol(), packed[:, :k]
                    )
                if head.getProbabilityCol():
                    out_frame = out_frame.with_column(
                        head.getProbabilityCol(), packed[:, k : 2 * k]
                    )
                if head.getPredictionCol():
                    out_frame = out_frame.with_column(
                        head.getPredictionCol(),
                        packed[:, 2 * k].astype(np.float64),
                    )
            return out_frame

        return finalize


def compile_pipeline(
    pipeline: PipelineModel,
    keep: Iterable[str] = (),
    fuse_heads: bool = True,
) -> PipelineModel:
    """Compile a fitted PipelineModel for serving: rewrite rules first
    (scaler folding), then each maximal run of registry-fusible stages
    (plus a terminating device-servable classifier head) becomes one
    :class:`FusedSegment`; everything else passes through eagerly.

    ``keep`` names intermediate columns to materialize even when only a
    fused stage consumes them; columns read by later eager stages are
    kept automatically.  ``fuse_heads=False`` restricts fusion to
    feature stages (the head stays a plain stage).
    """
    stages = fold_scalers(list(pipeline.getStages()))
    out: List[Transformer] = []
    i, n = 0, len(stages)
    while i < n:
        plan = device_plan_for(stages[i])
        if plan is None:
            out.append(stages[i])
            i += 1
            continue
        seg_stages: List[Transformer] = [stages[i]]
        seg_plans: List[DevicePlan] = [plan]
        seg_produced: set = set(plan.writes)
        seg_policies: dict = {
            r: plan.read_policy for r in plan.reads
        }
        i += 1
        while i < n:
            p = device_plan_for(stages[i])
            if p is None:
                break
            # a stage reading a shared EXTERNAL column under a different
            # upload policy than the run already requires would bypass
            # its own dtype guard (the first reader's cast wins at bind
            # time) — start a new segment instead, where the guard runs
            if any(
                r not in seg_produced
                and seg_policies.get(r, p.read_policy) != p.read_policy
                for r in p.reads
            ):
                break
            for r in p.reads:
                if r not in seg_produced:
                    seg_policies.setdefault(r, p.read_policy)
            seg_produced.update(p.writes)
            seg_stages.append(stages[i])
            seg_plans.append(p)
            i += 1
        head = None
        if fuse_heads and i < n and _fusible_head(stages[i]):
            head = stages[i]
            i += 1
        # single-upload rule: a fused VectorAssembler LEADING a segment
        # would turn the one packed upload into one upload per input
        # column — its host stack is the upload prep, so it runs eagerly
        while (
            seg_plans
            and isinstance(seg_stages[0], VectorAssembler)
            and len(seg_plans[0].reads) > 1
        ):
            out.append(seg_stages.pop(0))
            seg_plans.pop(0)
        if not seg_plans:
            if head is not None:
                out.append(head)
            continue
        later_reads = set(keep)
        for later in stages[i:]:
            later_reads.update(later.input_columns())
        seg = FusedSegment(
            seg_stages, seg_plans, head=head, keep=later_reads
        )
        # stable position among the plan's fused segments — the
        # execution context device-attributed errors carry (r18)
        seg.segment_index = sum(
            1 for s in out if isinstance(s, FusedSegment)
        )
        out.append(seg)
    return PipelineModel(stages=out)


def attach_device_domain(model, domain) -> int:
    """Hand a :class:`~sntc_tpu.resilience.device.DeviceFaultDomain`
    to every fused segment reachable from ``model`` (the
    BatchPredictor does this at construction and re-attaches on every
    hot-swap): segment-level compile failures then poison per
    (segment, signature) and HOST_DEGRADED diverts the fused programs
    to their eager path.  Returns the segment count."""
    segs = fused_segments(model)
    for i, seg in enumerate(segs):
        seg._domain = domain
        if seg.segment_index is None:
            seg.segment_index = i
    return len(segs)


def fused_segments(model) -> List[FusedSegment]:
    """Every FusedSegment reachable from ``model`` (PipelineModels are
    walked recursively; a BatchPredictor's wrapped model too)."""
    segs: List[FusedSegment] = []
    stack = [model]
    while stack:
        node = stack.pop()
        if isinstance(node, FusedSegment):
            segs.append(node)
        elif isinstance(node, PipelineModel):
            stack.extend(node.getStages())
        elif hasattr(node, "model") and isinstance(node.model, Transformer):
            stack.append(node.model)
    return segs


def fusion_stats(model) -> Optional[dict]:
    """Fusion evidence for ``pipeline_stats()``/bench: segment count,
    compile ledger, fallback count, and THIS model's transfer counters
    (per-segment sums — other fused models in the process don't leak
    in; the process-wide view lives in
    ``sntc_tpu.utils.profiling.transfer_ledger``).  None when the model
    contains no fused segment."""
    segs = fused_segments(model)
    if not segs:
        return None
    out = {
        "segments": len(segs),
        "fused_stages": sum(len(s.fused_stages) for s in segs),
        "compile_events": sum(s.compile_events for s in segs),
        "invocations": sum(s.invocations for s in segs),
        "fallbacks": sum(s.fallbacks for s in segs),
        "uploads": sum(s.uploads for s in segs),
        "downloads": sum(s.downloads for s in segs),
        "poisoned_signatures": sum(len(s._poisoned) for s in segs),
        "poisoned_served": sum(s.poisoned_served for s in segs),
    }
    # keyed per SEGMENT: two segments can compile identically-shaped
    # signatures, and a flat sig-keyed merge would attribute one
    # segment's device cost to the other
    costs = {
        f"segment{i}:{sig}": cost
        for i, s in enumerate(segs)
        for sig, cost in s.cost_analyses.items()
    }
    if costs:  # present only under SNTC_OBS_COST_ANALYSIS=1
        out["cost_analysis"] = costs
        roof = {}
        for i, s in enumerate(segs):
            for sig, cost in s.cost_analyses.items():
                secs, inv = s.cost_timings.get(sig, (0.0, 0))
                r = obs_cost.roofline(cost, secs, inv)
                if r is not None:
                    roof[f"segment{i}:{sig}"] = r
        if roof:
            out["roofline"] = roof
    from sntc_tpu.kernels.registry import kernel_stats

    out["kernels"] = kernel_stats()
    return out
