"""Compute-plane fault domain (r18): DEVICE kind classification and
injection round-trips, OOM-adaptive dispatch (recursive split + bucket
floor step-down), per-(segment, signature) compile poisoning + the
wall-time watchdog, the HOST_DEGRADED state machine with probe-gated
recovery and a churn-free compile ledger on re-entry, the host-fallback
equivalence matrix across all five heads (buckets + row-validity masks
+ salvage), engine-level no-death/no-strike behavior under every DEVICE
kind at every site, delivery-thread error-context threading, the
compile-cache fsck, the controller's platform-fault escalate
suppression, and the kill-mid-fallback chaos scenario."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import sntc_tpu.resilience as R
from sntc_tpu.core.base import Pipeline, PipelineModel, Transformer
from sntc_tpu.core.frame import Frame
from sntc_tpu.feature import DCT, MinMaxScaler, VectorAssembler
from sntc_tpu.fuse import compile_pipeline, fused_segments
from sntc_tpu.models import (
    LinearSVC,
    LogisticRegression,
    MultilayerPerceptronClassifier,
    NaiveBayes,
    RandomForestClassifier,
)
from sntc_tpu.resilience import (
    DeviceExecError,
    DeviceFaultDomain,
    DevicePolicy,
    InjectedDeviceFault,
    classify_device_error,
)
from sntc_tpu.resilience.device import annotate_batch
from sntc_tpu.serve import (
    MemorySink,
    MemorySource,
    ServeController,
    ServeDaemon,
    StreamingQuery,
    TenantSpec,
)
from sntc_tpu.serve.controller import SloSignal
from sntc_tpu.serve.transform import BatchPredictor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_state():
    R.clear()
    R.clear_events()
    R.reset_breakers()
    yield
    R.clear()
    R.clear_events()
    R.reset_breakers()


@pytest.fixture(autouse=True)
def _device_staged_path(monkeypatch):
    """Bitwise parity target: the eager fallback's staged transforms
    must run the DEVICE path (the f64 host-serve crossover is a
    different numerical path by design — the documented-tolerance case,
    not the bitwise one)."""
    monkeypatch.setenv("SNTC_SERVE_HOST_ROWS", "0")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Identity(Transformer):
    def transform(self, frame):
        return frame


def _domain(**kw):
    """A deterministic domain: synchronous always-healthy probe, zero
    probe interval (recovery on the first tick)."""
    policy = DevicePolicy(probe_interval_s=0.0, **kw)
    return DeviceFaultDomain(
        policy, probe_fn=lambda: True, probe_async=False
    )


def _frame(n=16):
    return Frame({"a": np.arange(float(n)), "b": np.arange(float(n)) * 2})


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


def test_injected_kinds_classify_round_trip():
    for kind in R.DEVICE_KINDS:
        R.arm("x.y", kind, times=1)
        with pytest.raises(InjectedDeviceFault) as ei:
            R.fault_point("x.y")
        assert classify_device_error(ei.value) == kind
        R.clear()


def test_classifies_real_xla_shapes_and_rejects_others():
    class XlaRuntimeError(RuntimeError):
        pass

    assert classify_device_error(XlaRuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "4294967296 bytes."
    )) == "device_oom"
    assert classify_device_error(XlaRuntimeError(
        "INTERNAL: during XLA compilation: something broke"
    )) == "compile_error"
    assert classify_device_error(XlaRuntimeError(
        "UNAVAILABLE: device lost: chip reset"
    )) == "device_lost"
    # the chain walks through wrappers
    try:
        try:
            raise XlaRuntimeError("RESOURCE_EXHAUSTED: Out of memory")
        except XlaRuntimeError as inner:
            raise RuntimeError("delivery failed") from inner
    except RuntimeError as outer:
        assert classify_device_error(outer) == "device_oom"
    # a non-XLA-shaped error never classifies, whatever its message
    assert classify_device_error(
        ValueError("compilation failed: out of memory")
    ) is None
    assert classify_device_error(None) is None


def test_device_kinds_inert_at_disk_and_data_hooks(tmp_path):
    R.arm("storage.wal", "device_oom")
    assert R.fault_disk("storage.wal") is None
    R.clear()
    R.arm("source.parse", "device_oom")
    assert R.fault_data("source.parse", b"abc") == b"abc"


# ---------------------------------------------------------------------------
# OOM-adaptive dispatch
# ---------------------------------------------------------------------------


def test_oom_split_bitwise_and_floor_step():
    f = _frame(16)
    ref = BatchPredictor(_Identity(), bucket_rows=4).predict_frame(f)
    dom = _domain()
    p = BatchPredictor(_Identity(), bucket_rows=4, device_domain=dom)
    R.arm("device.dispatch", "device_oom", times=1)
    out = p.predict_frame(f)
    for c in ref.columns:
        np.testing.assert_array_equal(np.asarray(out[c]),
                                      np.asarray(ref[c]))
    s = dom.stats()
    assert s["oom_splits"] == 1
    assert s["state"] == "DEVICE_OK"
    assert p.bucket_rows == 2  # floor stepped down under OOM pressure
    assert any(
        d["decision"] == "device_oom_split" for d in dom.journal
    )
    events = [e for e in R.recent_events()
              if e.get("event") == "device_oom_split"]
    assert events and events[0]["rows"] == 16


def test_oom_recursive_split_respects_depth_and_floor():
    """Persistent OOM splits to the floor, then counts the at-floor
    failure toward degradation and finishes on the host fallback —
    the dispatch NEVER dies."""
    dom = _domain(degrade_after=1)
    p = BatchPredictor(_Identity(), bucket_rows=4, device_domain=dom)
    R.arm("device.dispatch", "device_oom", times=None)
    out = p.predict_frame(_frame(16))
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.arange(16.0))
    assert dom.host_degraded
    assert dom.stats()["oom_splits"] >= 3  # halved all the way down
    assert dom.stats()["faults"]["device_oom"] >= 1
    # ONE floor step per top-level dispatch, not one per split level
    assert p.bucket_rows == 2
    # degraded serving skips the device fault surface entirely
    calls_before = R.call_count("device.dispatch")
    p.predict_frame(_frame(8))
    assert R.call_count("device.dispatch") == calls_before


# ---------------------------------------------------------------------------
# compile poisoning (+ watchdog)
# ---------------------------------------------------------------------------


def test_predict_compile_error_poisons_shape():
    f = _frame(16)
    ref = BatchPredictor(_Identity(), bucket_rows=4).predict_frame(f)
    dom = _domain()
    p = BatchPredictor(_Identity(), bucket_rows=4, device_domain=dom)
    R.arm("predict.compile", "compile_error", times=1)
    out = p.predict_frame(f)
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.asarray(ref["a"]))
    assert dom.stats()["poisoned_signatures"] == 1
    # the poisoned shape keeps serving the host path (no new compile
    # events), while a DIFFERENT shape still dispatches on device
    ce = p.compile_events
    p.predict_frame(f)
    assert p.compile_events == ce
    assert dom.stats()["fallback_batches"] >= 2
    p.predict_frame(_frame(64))
    assert p.compile_events == ce + 1


D = 4


def _fused_pipeline(mesh, head=None):
    """assembler (eager by the single-upload rule) + DCT + head → one
    real FusedSegment whose fuse.compile boundary genuinely fires."""
    rng = np.random.default_rng(0)
    X = np.abs(rng.normal(3.0, 2.0, size=(200, D))).astype(np.float32)
    cols = {f"c{i}": X[:, i].copy() for i in range(D)}
    cols["label"] = (X[:, 0] > 3.0).astype(np.float64)
    train = Frame(cols)
    head = head or LogisticRegression(
        mesh=mesh, featuresCol="dct", maxIter=20
    )
    pm = Pipeline(stages=[
        VectorAssembler(inputCols=[f"c{i}" for i in range(D)],
                        outputCol="features"),
        DCT(inputCol="features", outputCol="dct"),
        head,
    ]).fit(train)
    return pm, train.drop("label")


def test_fused_compile_error_poisons_exactly_that_signature(mesh8):
    pm, serve = _fused_pipeline(mesh8)
    ref = BatchPredictor(
        compile_pipeline(pm), bucket_rows=16
    ).predict_frame(serve.slice(0, 16))
    dom = _domain()
    fused = compile_pipeline(pm)
    p = BatchPredictor(fused, bucket_rows=16, device_domain=dom)
    seg = fused_segments(fused)[0]
    assert seg._domain is dom and seg.segment_index == 0
    R.arm("fuse.compile", "compile_error", times=1)
    out = p.predict_frame(serve.slice(0, 16))
    for c in ("rawPrediction", "probability", "prediction"):
        np.testing.assert_array_equal(
            np.asarray(out[c]), np.asarray(ref[c]), err_msg=c
        )
    assert len(seg._poisoned) == 1 and seg.compile_events == 0
    # same signature again: served poisoned, nothing compiles
    p.predict_frame(serve.slice(0, 16))
    assert seg.poisoned_served >= 1 and seg.compile_events == 0
    # a DIFFERENT signature compiles on device as usual
    p.predict_frame(serve.slice(0, 64))
    assert seg.compile_events == 1 and len(seg._poisoned) == 1
    ev = [e for e in R.recent_events()
          if e.get("event") == "signature_poisoned"]
    assert ev and ev[0]["segment"] == 0 and ev[0]["site"] == "fuse.compile"


def test_compile_watchdog_poisons_over_budget_signature(mesh8):
    pm, serve = _fused_pipeline(mesh8)
    ref = BatchPredictor(
        compile_pipeline(pm), bucket_rows=16
    ).predict_frame(serve.slice(0, 16))
    dom = DeviceFaultDomain(
        DevicePolicy(compile_budget_s=1e-9, probe_interval_s=0.0),
        probe_fn=lambda: True, probe_async=False,
    )
    fused = compile_pipeline(pm)
    p = BatchPredictor(fused, bucket_rows=16, device_domain=dom)
    seg = fused_segments(fused)[0]
    out = p.predict_frame(serve.slice(0, 16))
    for c in ("rawPrediction", "probability", "prediction"):
        np.testing.assert_array_equal(
            np.asarray(out[c]), np.asarray(ref[c]), err_msg=c
        )
    assert len(seg._poisoned) == 1
    assert any(
        d["decision"] == "signature_poisoned"
        and "watchdog" in d["reason"]
        for d in dom.journal
    )
    assert dom.state == "DEVICE_OK"  # poisoning is not degradation


# ---------------------------------------------------------------------------
# HOST_DEGRADED + probe-gated recovery
# ---------------------------------------------------------------------------


def test_device_lost_degrades_recovers_ledger_flat(mesh8):
    pm, serve = _fused_pipeline(mesh8)
    dom = _domain()
    fused = compile_pipeline(pm)
    p = BatchPredictor(fused, bucket_rows=16, device_domain=dom)
    seg = fused_segments(fused)[0]
    ref = p.predict_frame(serve.slice(0, 16))  # warm the device path
    ce_pred, ce_seg = p.compile_events, seg.compile_events
    from sntc_tpu.obs.metrics import registry

    R.arm("device.dispatch", "device_lost", times=1)
    out = p.predict_frame(serve.slice(0, 16))
    assert dom.host_degraded
    assert registry().get("sntc_device_state") == 1.0
    for c in ("prediction",):
        np.testing.assert_array_equal(np.asarray(out[c]),
                                      np.asarray(ref[c]))
    # degraded serving: host path, no compile churn
    p.predict_frame(serve.slice(0, 16))
    dom.tick()  # probe succeeds -> DEVICE_OK
    assert not dom.host_degraded
    assert registry().get("sntc_device_state") == 0.0
    assert dom.stats()["recoveries"] == 1
    assert dom.stats()["recovery_latency_s"] is not None
    # re-entry: the warm shapes/signatures reuse their programs
    p.predict_frame(serve.slice(0, 16))
    assert p.compile_events == ce_pred
    assert seg.compile_events == ce_seg
    ev = [e.get("event") for e in R.recent_events()]
    assert "device_degraded" in ev and "device_recovered" in ev


def test_health_maps_degrade_recover_pair():
    from sntc_tpu.resilience import HealthMonitor, HealthState

    h = HealthMonitor().attach()
    try:
        dom = _domain()
        dom.enter_host_degraded("test")
        assert h.state_of("model") == HealthState.DEGRADED
        dom.tick()
        assert h.state_of("model") == HealthState.OK
    finally:
        h.close()


def test_async_probe_never_blocks_tick():
    """The default probe path runs on a background thread; a hung probe
    leaves the domain degraded without wedging the tick."""
    import threading

    release = threading.Event()

    def slow_probe():
        release.wait(5.0)
        return True

    dom = DeviceFaultDomain(
        DevicePolicy(probe_interval_s=0.0), probe_fn=slow_probe,
        probe_async=True,
    )
    dom.enter_host_degraded("test")
    dom.tick()  # launches the probe; must return immediately
    assert dom.host_degraded
    release.set()
    deadline = 50
    import time as _t

    while dom.host_degraded and deadline:
        dom.tick()
        _t.sleep(0.02)
        deadline -= 1
    assert not dom.host_degraded


# ---------------------------------------------------------------------------
# host-fallback equivalence matrix (the tolerance contract's bitwise half)
# ---------------------------------------------------------------------------


def _heads(mesh):
    return {
        "lr": LogisticRegression(mesh=mesh, featuresCol="scaled",
                                 maxIter=20),
        "mlp": MultilayerPerceptronClassifier(
            mesh=mesh, featuresCol="scaled", layers=[D, 6, 2],
            maxIter=20,
        ),
        "nb": NaiveBayes(mesh=mesh, featuresCol="scaled",
                         modelType="multinomial"),
        "svc": LinearSVC(mesh=mesh, featuresCol="scaled", maxIter=20),
        "rf": RandomForestClassifier(mesh=mesh, featuresCol="scaled",
                                     numTrees=4, maxDepth=3, seed=0),
    }


@pytest.mark.parametrize("head_name", ["lr", "mlp", "nb", "svc", "rf"])
def test_host_fallback_equivalence_matrix(mesh8, head_name):
    """HOST_DEGRADED fallback vs the fused+bucketed device path for
    every head, with a row-validity (salvage admission) mask riding
    the dispatch: the f64 ``prediction`` column is BITWISE; the f32
    device-cast score columns hold the documented tolerance (XLA is
    free to fuse across the segment's stage boundary, so the device
    program's op order differs from the stage-by-stage host path by
    at most an ulp — docs/RESILIENCE.md tolerance table)."""
    rng = np.random.default_rng(1)
    X = np.abs(rng.normal(3.0, 2.0, size=(120, D))).astype(np.float32)
    cols = {f"c{i}": X[:, i].copy() for i in range(D)}
    cols["label"] = (X[:, 0] > 3.0).astype(np.float64)
    train = Frame(cols)
    pm = Pipeline(stages=[
        VectorAssembler(inputCols=[f"c{i}" for i in range(D)],
                        outputCol="features"),
        MinMaxScaler(inputCol="features", outputCol="scaled"),
        _heads(mesh8)[head_name],
    ]).fit(train)
    serve = train.drop("label").slice(0, 30)
    mask = np.ones(30, dtype=bool)
    mask[[3, 7, 21]] = False  # salvage-admission excisions
    device_out = BatchPredictor(
        compile_pipeline(pm), bucket_rows=16
    ).predict_frame(serve, row_valid=mask)
    dom = _domain()
    dom.enter_host_degraded("matrix")
    fallback_out = BatchPredictor(
        compile_pipeline(pm), bucket_rows=16, device_domain=dom
    ).predict_frame(serve, row_valid=mask)
    assert fallback_out.num_rows == device_out.num_rows == 27
    np.testing.assert_array_equal(
        np.asarray(fallback_out["prediction"]),
        np.asarray(device_out["prediction"]),
    )
    for c in ("rawPrediction", "probability"):
        if c in device_out and c in fallback_out:
            np.testing.assert_allclose(
                np.asarray(fallback_out[c]),
                np.asarray(device_out[c]),
                rtol=1e-5, atol=1e-6, err_msg=c,
            )
    assert dom.stats()["fallback_batches"] >= 1


# ---------------------------------------------------------------------------
# engine-level: no death, no strikes, exactly-once
# ---------------------------------------------------------------------------


def _engine_frames(n=6, rows=16):
    return [
        Frame({"a": np.arange(float(rows)) + 100 * i}) for i in range(n)
    ]


@pytest.mark.parametrize("site,kind", [
    ("device.dispatch", "device_oom"),
    ("device.dispatch", "device_lost"),
    ("predict.compile", "compile_error"),
    ("predict.compile", "device_lost"),
    ("fuse.compile", "compile_error"),
])
def test_engine_survives_device_kind_at_site(tmp_path, site, kind):
    """Each DEVICE kind armed at each site on a supervised stream:
    every batch commits, the engine never dies, and NOTHING
    quarantines or strikes (platform faults are not poison batches)."""
    frames = _engine_frames()
    dom = _domain(degrade_after=1)
    p = BatchPredictor(_Identity(), bucket_rows=8, device_domain=dom)
    q = StreamingQuery(
        p, MemorySource(frames), MemorySink(),
        str(tmp_path / "ckpt"), max_batch_offsets=1,
        max_batch_failures=3,
    )
    R.arm(site, kind, times=2)
    done = 0
    for _ in range(12):
        done += q.process_available()
        if done >= len(frames):
            break
    assert done == len(frames)
    events = [e.get("event") for e in R.recent_events()]
    assert "quarantine" not in events
    assert "breaker_open" not in events
    assert "retry_exhausted" not in events
    if kind != "compile_error":
        assert dom.stats()["faults"].get(kind, 0) >= 1 or \
            dom.stats()["oom_splits"] >= 1


def test_engine_pipeline_stats_device_block(tmp_path):
    dom = _domain()
    p = BatchPredictor(_Identity(), bucket_rows=8, device_domain=dom)
    q = StreamingQuery(
        p, MemorySource(_engine_frames(2)), MemorySink(),
        str(tmp_path / "ckpt"), max_batch_offsets=1,
    )
    q.process_available()
    stats = q.pipeline_stats()
    assert stats["device"]["state"] == "DEVICE_OK"
    assert "fallback_batches" in stats["device"]


def test_daemon_shared_domain_no_tenant_strikes(tmp_path):
    """A bare-site device fault hits every tenant's dispatches; the
    shared domain absorbs it once and NO tenant is struck — the ladder
    stays OK across the whole arc (degrade -> recover)."""
    model = _Identity()
    specs = [
        TenantSpec(tenant_id=t, model=model,
                   source=MemorySource(_engine_frames(3)),
                   sink=MemorySink(), max_batch_failures=2)
        for t in ("a", "b")
    ]
    daemon = ServeDaemon(
        specs, str(tmp_path / "root"), shape_buckets=8,
        device_policy=DevicePolicy(probe_interval_s=0.0,
                                   degrade_after=1),
    )
    # deterministic recovery: synchronous always-healthy probe
    daemon.device_domain._probe_fn = lambda: True
    daemon.device_domain._probe_async = False
    try:
        R.arm("device.dispatch", "device_lost", times=1)
        for _ in range(20):
            daemon.tick()
        st = daemon.status()
        assert st["aggregate"]["batches_done"] == 6
        for tid in ("a", "b"):
            assert st["tenants"][tid]["state"] == "OK"
            assert st["tenants"][tid]["strikes"] == 0
        dev = st["device"]
        assert dev["degradations"] == 1 and dev["recoveries"] == 1
        assert daemon.device_degraded() is False
    finally:
        daemon.close()


# ---------------------------------------------------------------------------
# delivery-thread error context (the r18 bugfix)
# ---------------------------------------------------------------------------


def test_device_exec_error_carries_context():
    e = DeviceExecError(
        "device device_oom while finalizing fused segment 2",
        kind="device_oom", segment=2, signature="((8, 4), '<f4')",
    )
    assert classify_device_error(e) == "device_oom"
    assert e.segment == 2 and "((8, 4)" in e.signature
    e2 = annotate_batch(e, 7)
    assert e2.batch_id == 7
    notes = getattr(e2, "__notes__", None)
    if notes is not None:  # py3.11+
        assert any("batch 7" in n for n in notes)
    # idempotent: a second annotate never overwrites the first
    annotate_batch(e2, 9)
    assert e2.batch_id == 7


def test_fused_finalize_error_names_segment_and_signature(
    mesh8, monkeypatch
):
    """A device-shaped error surfacing at FINALIZE (the overlap-sink
    delivery thread's stage) is re-raised as DeviceExecError naming
    the segment and input signature — and the engine's delivery wrapper
    adds the batch id."""
    import sntc_tpu.fuse.planner as planner

    pm, serve = _fused_pipeline(mesh8)
    fused = compile_pipeline(pm)
    dom = _domain(degrade_after=1)
    p = BatchPredictor(fused, bucket_rows=16, device_domain=dom)
    seg = fused_segments(fused)[0]

    class XlaRuntimeError(RuntimeError):
        pass

    real_span = planner.span

    def exploding_span(name, **kw):
        if name == "fuse.finalize":
            raise XlaRuntimeError("UNAVAILABLE: device lost: poof")
        return real_span(name, **kw)

    # the assembler runs eagerly ahead of the segment in the plan —
    # feed the segment its real input
    assembled = fused.getStages()[0].transform(serve.slice(0, 16))
    fin = seg.transform_async(assembled)
    monkeypatch.setattr(planner, "span", exploding_span)
    with pytest.raises(DeviceExecError) as ei:
        fin()
    monkeypatch.setattr(planner, "span", real_span)
    err = ei.value
    assert err.device_kind == "device_lost"
    assert err.segment == 0 and err.signature is not None
    assert "signature" in str(err) and "segment" in str(err)
    assert classify_device_error(err) == "device_lost"


def test_delivery_thread_device_error_redispatches_and_commits(
    tmp_path, monkeypatch, mesh8
):
    """Overlap-sink engine: a device-classified finalize failure on the
    delivery thread re-dispatches the head batch through the response
    ladder (domain degrades, fallback serves) — the batch COMMITS, no
    quarantine, and the device_fault event carries the batch id."""
    import sntc_tpu.fuse.planner as planner

    class XlaRuntimeError(RuntimeError):
        pass

    real_span = planner.span
    armed = {"n": 1}  # the first fused finalize dies device-shaped

    def exploding_span(name, **kw):
        if name == "fuse.finalize" and armed["n"] > 0:
            armed["n"] -= 1
            raise XlaRuntimeError("UNAVAILABLE: device lost: poof")
        return real_span(name, **kw)

    pm, serve = _fused_pipeline(mesh8)
    fused = compile_pipeline(pm)
    dom = _domain(degrade_after=1)
    p = BatchPredictor(fused, bucket_rows=16, device_domain=dom)
    frames = [serve.slice(i * 16, (i + 1) * 16) for i in range(3)]
    q = StreamingQuery(
        p, MemorySource(frames), MemorySink(),
        str(tmp_path / "ckpt"), max_batch_offsets=1,
        overlap_sink=True, pipeline_depth=2, max_batch_failures=3,
    )
    monkeypatch.setattr(planner, "span", exploding_span)
    done = 0
    for _ in range(10):
        done += q.process_available()
        if done >= 3:
            break
    monkeypatch.setattr(planner, "span", real_span)
    assert done == 3
    events = [e for e in R.recent_events()]
    names = [e.get("event") for e in events]
    assert "quarantine" not in names
    faults = [e for e in events if e.get("event") == "device_fault"]
    assert faults and any(e.get("batch_id") is not None for e in faults)
    assert dom.stats()["faults"].get("device_lost", 0) >= 1


def test_recovery_probe_runs_in_this_process(monkeypatch):
    """The default recovery probe is an in-process dispatch: the
    degraded server still holds the chip, so no second process may be
    needed to ask whether the device came back."""
    from sntc_tpu.resilience.device import probe_device

    def no_children(*a, **k):
        raise AssertionError("the recovery probe spawned a process")

    monkeypatch.setattr(subprocess, "run", no_children)
    monkeypatch.setattr(subprocess, "Popen", no_children)
    dom = DeviceFaultDomain(probe_async=False)  # default probe_fn
    dom.note_fault("device_lost", site="device.dispatch")
    assert dom.host_degraded
    dom.tick()
    assert not dom.host_degraded and dom.stats()["probes"] == 1
    # probe.init is its fault site: an armed fault is a still-dead
    # verdict, not an exception in the serving loop
    dom.note_fault("device_lost", site="device.dispatch")
    R.arm("probe.init", times=1)
    dom.tick()
    assert dom.host_degraded and dom.stats()["probes"] == 2
    R.clear()
    assert probe_device() is True


def test_consecutive_segment_compile_errors_degrade(mesh8):
    """Faults a fused segment ABSORBS (poison + eager fallback) still
    accumulate toward degrade_after: the enclosing dispatch's success
    must not reset the streak a fault it contains just started."""
    pm, serve = _fused_pipeline(mesh8)
    dom = _domain(degrade_after=2)
    p = BatchPredictor(compile_pipeline(pm), bucket_rows=0,
                       device_domain=dom)
    R.arm("fuse.compile", "compile_error", times=2)
    p.predict_frame(serve.slice(0, 16))  # fresh sig 1: poisons
    assert not dom.host_degraded
    assert dom.stats()["consecutive_faults"] == 1
    p.predict_frame(serve.slice(0, 32))  # fresh sig 2: poisons again
    assert dom.host_degraded  # 2 consecutive absorbed faults degrade


def test_half_open_breaker_slot_released_on_device_fault(tmp_path):
    """A device-classified dispatch failure must RELEASE the half-open
    probe slot allow() reserved (not record an outcome): a leaked slot
    would wedge the breaker half-open and deadlock the engine; a
    recorded failure would re-open it — a tenant-strike event — for a
    platform fault."""
    from sntc_tpu.resilience import CircuitBreaker

    clock = {"t": 0.0}
    br = CircuitBreaker(
        "predict.dispatch", window=4, min_calls=2,
        failure_threshold=0.5, cooldown_s=10.0,
        half_open_max_calls=1, clock=lambda: clock["t"],
    )
    br.record_failure()
    br.record_failure()  # -> OPEN
    assert br.state == "open"
    clock["t"] = 11.0  # cooldown elapsed -> HALF_OPEN on next touch
    dom = _domain(degrade_after=3)
    p = BatchPredictor(_Identity(), bucket_rows=0, device_domain=dom)
    q = StreamingQuery(
        p, MemorySource(_engine_frames(2, rows=1)), MemorySink(),
        str(tmp_path / "ckpt"), max_batch_offsets=1,
        max_batch_failures=3, breakers={"predict.dispatch": br},
    )
    # the probe dispatch dies device-shaped AT the bucket floor (1-row
    # batch: no split possible, not yet degraded → the terminal OOM
    # escapes to the engine): the engine defers WITHOUT scoring the
    # breaker
    R.arm("device.dispatch", "device_oom", times=1)
    q.process_available()
    assert br.state == "half_open"  # not re-opened by the platform fault
    assert br._probes_in_flight == 0  # the reserved slot was released
    # the next round's probe succeeds and closes the breaker — the
    # leak would have refused this call forever
    done = q.process_available()
    assert done == 2 and br.state == "closed"


def test_swap_model_clears_predictor_poisons():
    """A hot-swapped model earns a clean predictor-level plan cache:
    poisons belonged to the replaced model's programs."""
    dom = _domain()
    p = BatchPredictor(_Identity(), bucket_rows=4, device_domain=dom)
    R.arm("predict.compile", "compile_error", times=1)
    p.predict_frame(_frame(16))
    assert p._poisoned_shapes
    assert dom.stats()["poisoned_signatures"] == 1
    p.swap_model(_Identity())
    assert not p._poisoned_shapes
    # the LIVE gauge drops with the discarded programs
    assert dom.stats()["poisoned_signatures"] == 0
    fb = dom.stats()["fallback_batches"]
    p.predict_frame(_frame(16))  # back on the device path
    assert dom.stats()["fallback_batches"] == fb


def test_bucket_floor_restores_after_clean_streak():
    dom = DeviceFaultDomain(
        DevicePolicy(probe_interval_s=0.0, floor_restore_after=3),
        probe_fn=lambda: True, probe_async=False,
    )
    p = BatchPredictor(_Identity(), bucket_rows=8, device_domain=dom)
    R.arm("device.dispatch", "device_oom", times=1)
    p.predict_frame(_frame(16))
    assert p.bucket_rows == 4  # emergency step-down
    for _ in range(3):  # the pressure passed: clean streak restores
        p.predict_frame(_frame(16))
    assert p.bucket_rows == 8
    assert any(
        d["decision"] == "bucket_floor_restored" for d in dom.journal
    )


# ---------------------------------------------------------------------------
# controller: platform faults don't climb the tenant ladder
# ---------------------------------------------------------------------------


def test_controller_suppresses_escalate_while_platform_degraded(
    tmp_path,
):
    from sntc_tpu.resilience.control import ControlPolicy

    degraded = {"on": True}
    daemon = ServeDaemon(
        [
            TenantSpec(tenant_id="noisy", model=_Identity(),
                       source=MemorySource([]), sink=MemorySink(),
                       slo_max_shed_rate=0.05, quarantine_after=2),
            TenantSpec(tenant_id="quiet", model=_Identity(),
                       source=MemorySource([]), sink=MemorySink(),
                       slo_p99_ms=60_000.0),
        ],
        str(tmp_path / "root"),
    )
    ctl = ServeController.for_daemon(
        daemon, policy=ControlPolicy(confirm=1, cooldown=0),
        ingest=False, device_check=lambda: degraded["on"],
    )
    daemon.controller = ctl
    flooding = SloSignal(batches=2, rows=16, rows_per_s=16.0,
                         shed_offsets=20, shed_rate=0.9, backlog=30,
                         elapsed_s=1.0)
    try:
        seen = []
        for _ in range(24):
            rec = ctl.step({"noisy": flooding})
            if rec is not None and rec["action"] == "applied":
                seen.append(rec["knob"])
        # quota + shed rungs still steer; escalate NEVER fires
        assert "noisy/quota" in seen and "noisy/shed" in seen
        assert "noisy/escalate" not in seen
        assert ctl.escalations_total == 0
        assert ctl.platform_deferrals >= 1
        assert daemon._by_id["noisy"].strikes == 0
        assert ctl.stats()["platform_degraded"] is True
        # plane recovers -> the ladder is whole again
        degraded["on"] = False
        for _ in range(12):
            rec = ctl.step({"noisy": flooding})
            if rec is not None and rec["action"] == "applied":
                seen.append(rec["knob"])
        assert "noisy/escalate" in seen
    finally:
        daemon.close()


# ---------------------------------------------------------------------------
# compile-cache fsck
# ---------------------------------------------------------------------------


def test_fsck_compile_cache_quarantines_and_serving_recompiles(
    tmp_path,
):
    from sntc_tpu.utils.compile_cache import fsck_compile_cache

    cache = tmp_path / "xla_cache"
    cache.mkdir()
    (cache / "good_entry").write_bytes(b"\x28\xb5\x2f\xfd" + b"x" * 64)
    (cache / "torn_entry").write_bytes(b"")  # crash-mid-write shape
    (cache / "orphan.tmp").write_bytes(b"partial")
    report = fsck_compile_cache(str(cache))
    assert report["ok"]
    assert report["checked"] == 3
    assert [q["path"] for q in report["quarantined"]] == [
        str(cache / "torn_entry")
    ]
    assert os.path.exists(cache / ".corrupt" / "torn_entry")
    assert not os.path.exists(cache / "orphan.tmp")
    assert os.path.exists(cache / "good_entry")
    # idempotent: a second pass finds a clean cache
    again = fsck_compile_cache(str(cache))
    assert again["ok"] and not again["quarantined"]
    # report-only mode flags without moving
    (cache / "torn2").write_bytes(b"")
    ro = fsck_compile_cache(str(cache), repair=False)
    assert not ro["ok"] and not ro["quarantined"]
    # SEEDED POISONED-CACHE RECOVERY: serving over the doctored cache
    # dir recompiles cleanly (a fresh process with the cache armed)
    fsck_compile_cache(str(cache))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    env.pop("SNTC_NO_COMPILE_CACHE", None)  # conftest turns it off
    proc = subprocess.run(
        [sys.executable, "-c",
         "from sntc_tpu.utils.compile_cache import "
         "enable_persistent_cache\n"
         "import jax, jax.numpy as jnp\n"
         "d = enable_persistent_cache()\n"
         "out = jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0))\n"
         "print('served', float(out.sum()))\n"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "served" in proc.stdout


def test_fsck_cli_compile_cache_flag(tmp_path):
    cache = tmp_path / "cache"
    cache.mkdir()
    (cache / "dead").write_bytes(b"")
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "sntc_tpu", "fsck", str(ckpt),
         "--compile-cache-dir", str(cache), "--platform", "cpu"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout)
    assert report["compile_cache"]["quarantined"]
    assert os.path.exists(cache / ".corrupt" / "dead")


# ---------------------------------------------------------------------------
# chaos: kill mid-fallback (device.dispatch) in a real child process
# ---------------------------------------------------------------------------


def test_chaos_kill_mid_fallback_converges_bitwise(tmp_path):
    cm = _load_script("chaos_crash_matrix")
    ref = cm.run_device_reference(str(tmp_path))
    verdict = cm.run_device_kill_scenario(
        str(tmp_path), "device.dispatch", ref
    )
    assert verdict["ok"], verdict
    assert verdict["mid_fallback"] and verdict["sink_bitwise"]


@pytest.mark.slow
@pytest.mark.parametrize("site", ["predict.compile", "fuse.compile"])
def test_chaos_device_compile_kills_converge(tmp_path, site):
    cm = _load_script("chaos_crash_matrix")
    ref = cm.run_device_reference(str(tmp_path))
    verdict = cm.run_device_kill_scenario(str(tmp_path), site, ref)
    assert verdict["ok"], verdict
