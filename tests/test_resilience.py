"""Resilience layer (r6): retry policies with deterministic backoff,
fault injection at every wired site, streaming quarantine, checkpoint
corruption detection + fallback, CV fold tolerance, probe retries, and
the bench rendezvous-SIGABRT retry.  All tier-1 CPU — injected faults
stand in for real hardware failures."""

import json
import os
import sys

import numpy as np
import pytest

import sntc_tpu.resilience as R
from sntc_tpu.core.base import Estimator, Evaluator, Model, Transformer
from sntc_tpu.core.frame import Frame
from sntc_tpu.core.params import Param
from sntc_tpu.resilience import (
    InjectedFault,
    InjectedIOFault,
    RetryExhausted,
    RetryPolicy,
    with_retries,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    R.clear()
    R.clear_events()
    yield
    R.clear()
    R.clear_events()


# ---------------------------------------------------------------------------
# policy: deterministic backoff, executor semantics, events
# ---------------------------------------------------------------------------


def test_backoff_schedule_deterministic_and_exact():
    p = RetryPolicy(max_attempts=4, base_delay_s=0.1, multiplier=2.0,
                    max_delay_s=5.0, jitter=0.1, seed=3)
    sched = p.backoff_schedule()
    assert sched == p.backoff_schedule()  # pure function of the policy
    # asserted EXACTLY: base * mult^i * (1 + jitter * U[-1,1)) with the
    # policy's own seeded generator
    rng = np.random.default_rng(3)
    expected = [
        min(0.1 * 2.0**i, 5.0) * (1.0 + 0.1 * float(rng.uniform(-1, 1)))
        for i in range(3)
    ]
    assert sched == expected
    # zero jitter: the pure exponential ramp, capped
    flat = RetryPolicy(max_attempts=5, base_delay_s=1.0, multiplier=4.0,
                       max_delay_s=6.0, jitter=0.0).backoff_schedule()
    assert flat == [1.0, 4.0, 6.0, 6.0]


def test_with_retries_succeeds_and_sleeps_the_schedule():
    p = RetryPolicy(max_attempts=3, base_delay_s=0.2, jitter=0.1, seed=9)
    slept, calls = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise IOError("transient")
        return "ok"

    out = with_retries(flaky, p, site="t.site", sleep=slept.append)
    assert out == "ok" and len(calls) == 3
    assert slept == p.backoff_schedule()[:2]  # exact deterministic sleeps
    events = [e["event"] for e in R.recent_events(site="t.site")]
    assert events == ["retry", "retry", "retry_success"]


def test_with_retries_exhaustion_and_classifier():
    p = RetryPolicy(max_attempts=2, base_delay_s=0.0, jitter=0.0,
                    retryable=(IOError,))
    with pytest.raises(RetryExhausted) as ei:
        with_retries(lambda: (_ for _ in ()).throw(IOError("x")), p,
                     site="t.ex", sleep=lambda d: None)
    assert isinstance(ei.value.last_exception, IOError)
    assert ei.value.attempts == 2
    assert [e["event"] for e in R.recent_events(site="t.ex")] == [
        "retry", "retry_exhausted"
    ]

    # non-retryable exceptions pass through unchanged, no events
    with pytest.raises(KeyError):
        with_retries(lambda: {}["k"], p, site="t.nr", sleep=lambda d: None)
    assert R.recent_events(site="t.nr") == []


def test_with_retries_deadline_clamps_final_sleep():
    """The 100s backoff cannot fit the 50s deadline: the final sleep is
    CLAMPED to exactly the remaining budget (never slept past the
    deadline, never given up with budget left) and the last attempt
    runs at the deadline."""
    p = RetryPolicy(max_attempts=10, base_delay_s=100.0,
                    max_delay_s=100.0, jitter=0.0, deadline_s=50.0)
    t = {"now": 0.0}
    slept = []

    def sleep(d):
        slept.append(d)
        t["now"] += d

    def fail():
        raise IOError("x")

    with pytest.raises(RetryExhausted):
        with_retries(fail, p, site="t.dl", sleep=sleep,
                     clock=lambda: t["now"])
    assert slept == [50.0]  # clamped to remaining deadline, not 100
    assert t["now"] == 50.0  # total elapsed never exceeds the deadline
    ex = R.recent_events(site="t.dl", event="retry_exhausted")
    assert len(ex) == 1 and ex[0]["attempts"] == 2 and ex[0]["deadline_hit"]


def test_events_jsonl_sink(tmp_path, monkeypatch):
    log = tmp_path / "resilience.jsonl"
    monkeypatch.setenv("SNTC_RESILIENCE_LOG", str(log))
    p = RetryPolicy(max_attempts=2, base_delay_s=0.0, jitter=0.0)
    with pytest.raises(RetryExhausted):
        with_retries(lambda: 1 / 0, p, site="t.log", sleep=lambda d: None)
    records = [json.loads(ln) for ln in log.read_text().splitlines()]
    assert [r["event"] for r in records] == ["retry", "retry_exhausted"]
    assert all(r["site"] == "t.log" for r in records)


# ---------------------------------------------------------------------------
# faults: registry, schedules, env grammar
# ---------------------------------------------------------------------------


def test_fault_point_unarmed_is_noop():
    R.fault_point("sink.write")  # nothing armed: must not raise


def test_arm_nth_call_and_times():
    R.arm("sink.write", kind="io", after=1, times=1)
    R.fault_point("sink.write")  # call 1: let through
    with pytest.raises(InjectedIOFault):
        R.fault_point("sink.write")  # call 2: fires
    R.fault_point("sink.write")  # times=1 spent
    assert R.call_count("sink.write") == 3
    injected = R.recent_events(site="sink.write", event="fault_injected")
    assert len(injected) == 1 and injected[0]["call"] == 2


def test_env_grammar_parses_and_rejects():
    specs = R.parse_faults_env("sink.write:io:0.3:7, probe.init")
    assert specs == [
        {"site": "sink.write", "kind": "io", "prob": 0.3, "seed": 7},
        {"site": "probe.init"},
    ]
    with pytest.raises(ValueError, match="malformed"):
        R.parse_faults_env("a:b:c")
    with pytest.raises(ValueError, match="malformed"):
        R.parse_faults_env("a:exc:0.5:1:9")


def test_env_knob_arms_deterministically(monkeypatch):
    monkeypatch.setenv("SNTC_FAULTS", "stream.read:timeout:0.5:11")
    fired = []
    for _ in range(20):
        try:
            R.fault_point("stream.read")
            fired.append(0)
        except R.InjectedTimeoutFault:
            fired.append(1)
    # the same env string must reproduce the same fault sequence
    rng = np.random.default_rng(11)
    expected = [1 if float(rng.uniform()) < 0.5 else 0 for _ in range(20)]
    assert fired == expected
    # dropping the env disarms on the next call
    monkeypatch.delenv("SNTC_FAULTS")
    R.fault_point("stream.read")


# ---------------------------------------------------------------------------
# streaming: per-batch retry, dead-letter quarantine, atomic sink
# ---------------------------------------------------------------------------


class _Identity(Transformer):
    def transform(self, frame):
        return frame


def _frames(n_batches, rows=8):
    return [
        Frame({"x": np.arange(rows, dtype=np.float64) + 100 * b})
        for b in range(n_batches)
    ]


def _query(tmp_path, src_frames, sink=None, **kw):
    from sntc_tpu.serve import MemorySink, MemorySource, StreamingQuery

    src = MemorySource(src_frames)
    sink = sink if sink is not None else MemorySink()
    q = StreamingQuery(
        _Identity(), src, sink, str(tmp_path / "ckpt"),
        max_batch_offsets=1, **kw,
    )
    return q, sink


def test_streaming_sink_retry_under_policy(tmp_path):
    R.arm("sink.write", after=1, times=2)  # batch 1 fails twice, then ok
    policy = RetryPolicy(max_attempts=3, base_delay_s=0.0, jitter=0.0)
    q, sink = _query(tmp_path, _frames(3), retry_policy=policy)
    assert q.process_available() == 3  # completes despite the faults
    assert [i for i, _ in sink.batches] == [0, 1, 2]
    assert len(R.recent_events(site="sink.write", event="retry")) == 2
    assert R.recent_events(site="sink.write", event="retry_success")


def test_streaming_source_read_retry_under_policy(tmp_path):
    R.arm("stream.read", times=1)
    policy = RetryPolicy(max_attempts=2, base_delay_s=0.0, jitter=0.0)
    q, sink = _query(tmp_path, _frames(2), retry_policy=policy)
    assert q.process_available() == 2
    assert len(sink.frames) == 2
    assert R.recent_events(site="stream.read", event="retry")


def test_streaming_poison_batch_quarantined_query_continues(tmp_path):
    from sntc_tpu.serve import MemorySink

    class PoisonSink(MemorySink):
        def add_batch(self, batch_id, frame):
            if batch_id == 1:
                raise ValueError("poison batch")
            super().add_batch(batch_id, frame)

    policy = RetryPolicy(max_attempts=2, base_delay_s=0.0, jitter=0.0)
    q, sink = _query(
        tmp_path, _frames(4), sink=PoisonSink(),
        retry_policy=policy, max_batch_failures=1,
    )
    # the query drains ALL batches in one call — no exception escapes
    assert q.process_available() == 4
    assert [i for i, _ in sink.batches] == [0, 2, 3]
    assert q.last_committed() == 3

    # dead-letter journal holds the evidence
    dl = os.path.join(str(tmp_path / "ckpt"), "dead_letter")
    records = [
        json.loads(ln)
        for ln in open(os.path.join(dl, "dead_letter.jsonl"))
    ]
    assert len(records) == 1
    rec = records[0]
    assert rec["batch_id"] == 1 and "poison" in rec["error"]
    assert rec["intent"]["start"] == 1 and rec["intent"]["end"] == 2
    assert rec["rows_file"] and os.path.exists(
        os.path.join(dl, rec["rows_file"])
    )
    # progress marks the quarantined batch; quarantine event emitted
    quarantined = [p for p in q.recentProgress if p.get("quarantined")]
    assert [p["batchId"] for p in quarantined] == [1]
    assert R.recent_events(site="sink.write", event="quarantine")

    # a restarted query on the same checkpoint does NOT replay batch 1
    q2, sink2 = _query(tmp_path, _frames(4), retry_policy=policy,
                       max_batch_failures=1)
    assert q2.process_available() == 0


def test_streaming_quarantine_threshold_counts_rounds(tmp_path):
    """max_batch_failures=2: the first failed retirement round DEFERS
    (batch stays queued, engine loop stays alive — no exception), the
    second quarantines and the query continues."""
    from sntc_tpu.serve import MemorySink

    class AlwaysFail(MemorySink):
        def add_batch(self, batch_id, frame):
            if batch_id == 0:
                raise IOError("down")
            super().add_batch(batch_id, frame)

    q, sink = _query(tmp_path, _frames(2), sink=AlwaysFail(),
                     max_batch_failures=2)
    assert q.process_available() == 0  # round 1: fails, stays queued
    assert q.last_committed() == -1
    assert q.process_available() == 2  # round 2: quarantined + continue
    assert [i for i, _ in sink.batches] == [1]


def test_streaming_background_loop_survives_quarantine(tmp_path):
    """The start()/awaitTermination surface must DEGRADE, not die, when
    quarantine is armed: each poll tick is one retry round and the
    poison batch dead-letters without crashing the loop thread."""
    import time as _time

    from sntc_tpu.serve import MemorySink

    class PoisonSink(MemorySink):
        def add_batch(self, batch_id, frame):
            if batch_id == 1:
                raise ValueError("poison")
            super().add_batch(batch_id, frame)

    q, sink = _query(tmp_path, _frames(3), sink=PoisonSink(),
                     max_batch_failures=2)
    q.start(poll_interval=0.01)
    deadline = _time.time() + 30
    while _time.time() < deadline and q.last_committed() < 2:
        _time.sleep(0.01)
    assert q.last_committed() == 2
    assert q.isActive  # the loop thread survived the poison batch
    q.stop()
    assert [i for i, _ in sink.batches] == [0, 2]


def test_streaming_read_poison_batch_quarantined(tmp_path):
    """A batch whose SOURCE READ fails persistently quarantines too —
    the query must not wedge forever on a torn input file."""
    from sntc_tpu.serve import MemorySink, MemorySource, StreamingQuery

    class PoisonSource(MemorySource):
        def get_batch(self, start, end):
            if start == 1:
                raise IOError("torn input file")
            return super().get_batch(start, end)

    policy = RetryPolicy(max_attempts=2, base_delay_s=0.0, jitter=0.0)
    src = PoisonSource(_frames(3))
    sink = MemorySink()
    q = StreamingQuery(
        _Identity(), src, sink, str(tmp_path / "ckpt"),
        max_batch_offsets=1, retry_policy=policy, max_batch_failures=1,
    )
    assert q.process_available() == 3  # all three batches commit
    assert [i for i, _ in sink.batches] == [0, 2]
    assert q.last_committed() == 2
    rec = json.loads(open(os.path.join(
        str(tmp_path / "ckpt"), "dead_letter", "dead_letter.jsonl"
    )).read().strip())
    assert rec["batch_id"] == 1 and rec["rows_file"] is None
    assert [
        p["batchId"] for p in q.recentProgress if p.get("quarantined")
    ] == [1]


def test_streaming_predict_poison_batch_quarantined(tmp_path):
    """A batch the MODEL cannot process (malformed rows) quarantines
    with its raw rows journaled — the most common poison-batch shape."""
    class PickyModel(Transformer):
        def transform(self, frame):
            if 100.0 <= float(np.asarray(frame["x"])[0]) < 200.0:
                raise ValueError("malformed features")  # batch 1 only
            return frame

    from sntc_tpu.serve import MemorySink, MemorySource, StreamingQuery

    src = MemorySource(_frames(3))
    sink = MemorySink()
    q = StreamingQuery(
        PickyModel(), src, sink, str(tmp_path / "ckpt"),
        max_batch_offsets=1, max_batch_failures=1,
    )
    assert q.process_available() == 3
    assert [i for i, _ in sink.batches] == [0, 2]
    rec = json.loads(open(os.path.join(
        str(tmp_path / "ckpt"), "dead_letter", "dead_letter.jsonl"
    )).read().strip())
    assert rec["batch_id"] == 1
    # the poison rows themselves are preserved for repair tooling
    assert rec["rows_file"] and rec["num_rows"] == 8
    events = R.recent_events(site="predict.dispatch", event="quarantine")
    assert len(events) == 1


def test_streaming_failure_stages_count_separately(tmp_path):
    """A read flake and a sink flake on the same batch must not pool
    toward one quarantine threshold."""
    from sntc_tpu.serve import MemorySink, MemorySource, StreamingQuery

    class FlakyBoth(MemorySource):
        def __init__(self, frames):
            super().__init__(frames)
            self.read_fails = 1

        def get_batch(self, start, end):
            if start == 0 and self.read_fails:
                self.read_fails -= 1
                raise IOError("read flake")
            return super().get_batch(start, end)

    class FlakySink(MemorySink):
        def __init__(self):
            super().__init__()
            self.sink_fails = 1

        def add_batch(self, batch_id, frame):
            if batch_id == 0 and self.sink_fails:
                self.sink_fails -= 1
                raise IOError("sink flake")
            super().add_batch(batch_id, frame)

    src = FlakyBoth(_frames(1))
    sink = FlakySink()
    q = StreamingQuery(
        _Identity(), src, sink, str(tmp_path / "ckpt"),
        max_batch_offsets=1, max_batch_failures=2,
    )
    # round 1: read fails (read=1/2, deferred); round 2: read ok, sink
    # fails (sink=1/2, deferred); round 3: delivered — NOT quarantined,
    # because neither stage reached its own threshold
    assert q.process_available() == 0
    assert q.process_available() == 0
    assert q.process_available() == 1
    assert [i for i, _ in sink.batches] == [0]
    assert not R.recent_events(event="quarantine")


def test_streaming_defaults_preserve_single_shot(tmp_path):
    """No retry_policy / max_batch_failures: an armed fault propagates
    exactly as a real failure did pre-resilience (r5 contract)."""
    R.arm("sink.write", times=1)
    q, sink = _query(tmp_path, _frames(2))
    with pytest.raises(InjectedFault):
        q.process_available()
    assert q.process_available() == 2  # WAL replay still exact


def test_csv_sink_atomic_no_tmp_left(tmp_path):
    from sntc_tpu.serve import CsvDirSink

    out = str(tmp_path / "out")
    sink = CsvDirSink(out, columns=["x"])
    sink.add_batch(0, Frame({"x": np.arange(4, dtype=np.float64)}))
    assert os.listdir(out) == ["batch_000000.csv"]  # no .tmp debris


# ---------------------------------------------------------------------------
# checkpointing: manifest, corruption detection, fallback
# ---------------------------------------------------------------------------


def _stage():
    from sntc_tpu.feature import IndexToString

    return IndexToString(inputCol="p", outputCol="s", labels=["x", "y"])


def test_save_writes_manifest_and_roundtrips(tmp_path):
    from sntc_tpu.mlio import load_model, save_model
    from sntc_tpu.mlio.save_load import verify_checkpoint

    path = save_model(_stage(), str(tmp_path / "m"))
    assert os.path.exists(os.path.join(path, "_manifest.json"))
    assert verify_checkpoint(path) is True
    loaded = load_model(path)
    assert loaded.getLabels() == ["x", "y"]


def test_corrupted_checkpoint_detected(tmp_path):
    from sntc_tpu.mlio import save_model
    from sntc_tpu.mlio.save_load import (
        CheckpointCorruptError,
        load_model,
    )

    path = save_model(_stage(), str(tmp_path / "m"))
    meta = os.path.join(path, "metadata.json")
    blob = open(meta, "rb").read()
    with open(meta, "wb") as f:  # same length, flipped bytes: torn write
        f.write(blob[:-4] + b"XXXX")
    with pytest.raises(CheckpointCorruptError, match="sha256 mismatch"):
        load_model(path, fallback=False)


def test_corrupted_checkpoint_falls_back_to_prev(tmp_path, capsys):
    from sntc_tpu.feature import IndexToString
    from sntc_tpu.mlio import load_model, save_model

    path = str(tmp_path / "m")
    save_model(
        IndexToString(inputCol="p", outputCol="s", labels=["old"]), path
    )
    save_model(
        IndexToString(inputCol="p", outputCol="s", labels=["new"]), path
    )
    assert os.path.isdir(path + ".prev")  # previous good snapshot kept
    assert load_model(path).getLabels() == ["new"]

    # corrupt the primary: load degrades to the .prev snapshot
    meta = os.path.join(path, "metadata.json")
    blob = open(meta, "rb").read()
    with open(meta, "wb") as f:
        f.write(blob[:-4] + b"XXXX")
    loaded = load_model(path)
    assert loaded.getLabels() == ["old"]
    assert "degraded to previous good snapshot" in capsys.readouterr().err
    assert R.recent_events(site="ckpt.load", event="ckpt_fallback")


def test_injected_load_fault_takes_fallback_path(tmp_path):
    """An armed ckpt.load fault must degrade to .prev exactly as a real
    load failure does (the fault simulates flaky checkpoint storage)."""
    from sntc_tpu.feature import IndexToString
    from sntc_tpu.mlio import load_model, save_model

    path = str(tmp_path / "m")
    save_model(
        IndexToString(inputCol="p", outputCol="s", labels=["old"]), path
    )
    save_model(
        IndexToString(inputCol="p", outputCol="s", labels=["new"]), path
    )
    R.arm("ckpt.load", times=1)
    assert load_model(path).getLabels() == ["old"]  # degraded to .prev
    assert R.recent_events(site="ckpt.load", event="ckpt_fallback")
    # without a .prev the fault propagates
    R.arm("ckpt.load", times=1)
    lone = save_model(_stage(), str(tmp_path / "lone"))
    with pytest.raises(InjectedFault):
        load_model(lone)


def test_injected_save_fault_leaves_old_checkpoint_intact(tmp_path):
    from sntc_tpu.feature import IndexToString
    from sntc_tpu.mlio import load_model, save_model

    path = str(tmp_path / "m")
    save_model(
        IndexToString(inputCol="p", outputCol="s", labels=["good"]), path
    )
    R.arm("ckpt.save", times=1)
    with pytest.raises(InjectedFault):
        save_model(
            IndexToString(inputCol="p", outputCol="s", labels=["bad"]),
            path,
        )
    # the atomic publish never happened: live checkpoint is untouched,
    # no staging debris remains
    assert load_model(path).getLabels() == ["good"]
    assert [d for d in os.listdir(tmp_path) if ".tmp-" in d] == []


def test_ckpt_save_retry_under_policy_completes(tmp_path):
    """Acceptance: with ckpt.save armed, a save under with_retries
    completes and the round-trip load succeeds."""
    from sntc_tpu.mlio import load_model, save_model

    R.arm("ckpt.save", times=1)
    policy = RetryPolicy(max_attempts=2, base_delay_s=0.0, jitter=0.0)
    path = with_retries(
        lambda: save_model(_stage(), str(tmp_path / "m")),
        policy, site="ckpt.save",
    )
    assert load_model(path).getLabels() == ["x", "y"]
    assert R.recent_events(site="ckpt.save", event="retry_success")


def test_torn_write_size_mismatch_detected(tmp_path):
    from sntc_tpu.mlio import save_model
    from sntc_tpu.mlio.save_load import (
        CheckpointCorruptError,
        verify_checkpoint,
    )

    path = save_model(_stage(), str(tmp_path / "m"))
    meta = os.path.join(path, "metadata.json")
    with open(meta, "ab") as f:
        f.write(b"garbage")  # truncation/extension: size check catches
    with pytest.raises(CheckpointCorruptError, match="bytes"):
        verify_checkpoint(path)


def test_missing_manifest_loads_unverified(tmp_path):
    """Pre-resilience checkpoints (no manifest) still load."""
    from sntc_tpu.mlio import load_model, save_model
    from sntc_tpu.mlio.save_load import verify_checkpoint

    path = save_model(_stage(), str(tmp_path / "m"))
    os.remove(os.path.join(path, "_manifest.json"))
    assert verify_checkpoint(path) is False
    assert load_model(path).getLabels() == ["x", "y"]


# ---------------------------------------------------------------------------
# CrossValidator fold tolerance
# ---------------------------------------------------------------------------


class _ConstParams:
    value = Param("constant prediction", default=0.0)


class ConstModel(_ConstParams, Model):
    def __init__(self, value=0.0, **kw):
        super().__init__(**kw)
        self.value = float(value)

    def transform(self, frame):
        return frame.with_column(
            "prediction", np.full(frame.num_rows, self.value)
        )


class ConstEstimator(_ConstParams, Estimator):
    def _fit(self, frame):
        return ConstModel(value=float(self.getValue()))


class MeanEvaluator(Evaluator):
    def evaluate(self, frame):
        return float(np.mean(frame["prediction"]))


def _cv(fault_tolerant=True, retry_policy=None, folds=2):
    from sntc_tpu.tuning import CrossValidator

    return CrossValidator(
        estimator=ConstEstimator(),
        estimatorParamMaps=[{"value": 1.0}, {"value": 3.0}],
        evaluator=MeanEvaluator(),
        numFolds=folds,
        seed=0,
        faultTolerant=fault_tolerant,
        retryPolicy=retry_policy,
    )


def _cv_frame(n=40):
    return Frame({"x": np.arange(n, dtype=np.float64)})


def test_cv_cell_failure_records_nan_and_search_survives():
    R.arm("cv.fit", after=0, times=1)  # first cell (fold 0, grid 0) dies
    cv = _cv(retry_policy=RetryPolicy(max_attempts=1))
    model = cv.fit(_cv_frame())
    # grid point 1 (value=3.0) wins; point 0 averaged over its one
    # surviving fold
    assert model.bestIndex == 1
    assert model.avgMetrics == [1.0, 3.0]
    degraded = R.recent_events(site="cv.fit", event="cv_cell_degraded")
    assert len(degraded) == 1
    assert degraded[0]["fold"] == 0 and degraded[0]["grid_index"] == 0


def test_cv_cell_retry_heals_transient_failure():
    R.arm("cv.fit", times=1)
    cv = _cv(retry_policy=RetryPolicy(
        max_attempts=2, base_delay_s=0.0, jitter=0.0
    ))
    model = cv.fit(_cv_frame())
    assert model.avgMetrics == [1.0, 3.0]
    assert not R.recent_events(site="cv.fit", event="cv_cell_degraded")
    assert R.recent_events(site="cv.fit", event="retry_success")


def test_cv_all_cells_failing_raises():
    R.arm("cv.fit", prob=1.0, times=None)
    cv = _cv(retry_policy=RetryPolicy(max_attempts=1))
    with pytest.raises(RuntimeError, match="every .* cell failed"):
        cv.fit(_cv_frame())


def test_cv_not_fault_tolerant_propagates():
    R.arm("cv.fit", times=1)
    cv = _cv(fault_tolerant=False)
    # the sequential non-tolerant path never calls the fault point (it
    # predates the resilience layer) — but an estimator failure aborts
    class Boom(ConstEstimator):
        def _fit(self, frame):
            raise RuntimeError("fit boom")

    from sntc_tpu.tuning import CrossValidator

    cv = CrossValidator(
        estimator=Boom(), estimatorParamMaps=[{}],
        evaluator=MeanEvaluator(), numFolds=2,
    )
    with pytest.raises(RuntimeError, match="fit boom"):
        cv.fit(_cv_frame())


def test_cv_fault_tolerant_matches_clean_run_metrics():
    """No faults armed: the tolerant path computes the same grid."""
    model_ft = _cv(fault_tolerant=True).fit(_cv_frame())
    model_plain = _cv(fault_tolerant=False).fit(_cv_frame())
    assert model_ft.avgMetrics == model_plain.avgMetrics
    assert model_ft.bestIndex == model_plain.bestIndex


# ---------------------------------------------------------------------------
# acceptance: SNTC_FAULTS arming each wired site in turn — streaming,
# checkpoint round-trip, CV grid all complete (retry or degrade per
# policy) with structured events (ISSUE r6 criterion 3)
# ---------------------------------------------------------------------------

# seed 29 uniform draws: .050 .506 .519 .265 .129 .021 .394 ... — with
# prob 0.5 the fire/clear sequence below is fully deterministic


def test_env_faults_streaming_query_completes(monkeypatch, tmp_path):
    monkeypatch.setenv("SNTC_FAULTS", "sink.write:io:0.5:29")
    policy = RetryPolicy(max_attempts=2, base_delay_s=0.0, jitter=0.0)
    q, sink = _query(tmp_path, _frames(3), retry_policy=policy,
                     max_batch_failures=1)
    # batch 0: fire, retry clears; batch 1: clears; batch 2: fire, fire
    # -> retry exhausted -> quarantined.  The query still drains fully.
    assert q.process_available() == 3
    assert [i for i, _ in sink.batches] == [0, 1]
    assert [
        p["batchId"] for p in q.recentProgress if p.get("quarantined")
    ] == [2]
    assert R.recent_events(site="sink.write", event="retry_success")
    assert R.recent_events(site="sink.write", event="quarantine")


def test_env_faults_checkpoint_roundtrip_completes(monkeypatch, tmp_path):
    from sntc_tpu.mlio import load_model, save_model

    monkeypatch.setenv("SNTC_FAULTS", "ckpt.save:exc:0.5:29")
    policy = RetryPolicy(max_attempts=2, base_delay_s=0.0, jitter=0.0)
    # save attempt 1 draws .050 -> injected fault; retry draws .506 ->
    # clean save.  Round-trip load verifies the manifest.
    path = with_retries(
        lambda: save_model(_stage(), str(tmp_path / "m")),
        policy, site="ckpt.save",
    )
    assert load_model(path).getLabels() == ["x", "y"]
    assert R.recent_events(site="ckpt.save", event="fault_injected")
    assert R.recent_events(site="ckpt.save", event="retry_success")


def test_env_faults_cv_grid_completes(monkeypatch):
    monkeypatch.setenv("SNTC_FAULTS", "cv.fit:exc:0.5:29")
    cv = _cv(retry_policy=RetryPolicy(
        max_attempts=2, base_delay_s=0.0, jitter=0.0
    ))
    # cells in order: (f0,g0) fire+retry-ok, (f0,g1) ok, (f1,g0)
    # fire+fire -> NaN, (f1,g1) fire+fire -> NaN.  Fold-0 metrics alone
    # still rank the grid; the search completes.
    model = cv.fit(_cv_frame())
    assert model.avgMetrics == [1.0, 3.0]
    assert model.bestIndex == 1
    degraded = R.recent_events(site="cv.fit", event="cv_cell_degraded")
    assert [(d["fold"], d["grid_index"]) for d in degraded] == [
        (1, 0), (1, 1)
    ]
    assert R.recent_events(site="cv.fit", event="retry_success")


def test_malformed_faults_env_warns_not_raises(monkeypatch, capsys):
    """A typo'd SNTC_FAULTS must fail loud ONCE on stderr and arm
    nothing — raising from fault_point would be misclassified as a
    site failure by the retry/quarantine machinery."""
    monkeypatch.setenv("SNTC_FAULTS", "sink.write:oi:0.3")  # bad kind
    R.fault_point("sink.write")  # no raise
    R.fault_point("stream.read")
    assert "malformed SNTC_FAULTS" in capsys.readouterr().err
    # the warning is once per string, not per call
    R.fault_point("sink.write")
    assert "malformed" not in capsys.readouterr().err


# ---------------------------------------------------------------------------
# collective dispatch site
# ---------------------------------------------------------------------------


def test_collective_dispatch_fault_and_retry(monkeypatch):
    import sntc_tpu.parallel.collectives as col

    # stub the jit so the test exercises the dispatch wrapper, not XLA
    monkeypatch.setattr(col.jax, "jit", lambda f: (lambda *a: "ok"))

    agg = col.make_tree_aggregate(lambda x: x, mesh=None)
    R.arm("collective.dispatch", times=1)
    with pytest.raises(InjectedFault):
        agg(np.zeros(4))  # single-shot by default

    R.clear()
    R.arm("collective.dispatch", times=1)
    monkeypatch.setenv("SNTC_COLLECTIVE_RETRIES", "1")
    agg = col.make_tree_aggregate(lambda x: x, mesh=None)
    assert agg(np.zeros(4)) == "ok"  # retried through the fault
    assert R.recent_events(
        site="collective.dispatch", event="retry_success"
    )


# ---------------------------------------------------------------------------
# bench: rendezvous-SIGABRT retry (exactly once, journaled)
# ---------------------------------------------------------------------------


def _bench():
    sys.path.insert(0, REPO)
    import bench

    return bench


_RENDEZVOUS_STDERR = (
    "F0000 00:00 external/xla/xla/... Expected 8 threads to join the "
    "rendezvous, but only 5 of them arrived on time; aborted"
)


def test_is_rendezvous_abort_signature():
    bench = _bench()
    assert bench._is_rendezvous_abort(-6, _RENDEZVOUS_STDERR)
    assert bench._is_rendezvous_abort(134, _RENDEZVOUS_STDERR)
    assert not bench._is_rendezvous_abort(0, _RENDEZVOUS_STDERR)
    assert not bench._is_rendezvous_abort(-6, "segfault somewhere")
    assert not bench._is_rendezvous_abort(1, _RENDEZVOUS_STDERR)


class _Args:
    rows = 100
    no_pair = False
    platform = "cpu"


class _Proc:
    def __init__(self, returncode, stdout="", stderr=""):
        self.returncode = returncode
        self.stdout = stdout
        self.stderr = stderr


def test_bench_isolated_retries_rendezvous_once():
    bench = _bench()
    good = json.dumps({"metric": "m", "value": 1.0, "unit": "s"})
    procs = [_Proc(-6, stderr=_RENDEZVOUS_STDERR), _Proc(0, stdout=good)]
    calls = []

    def runner(cmd, **kw):
        calls.append(cmd)
        return procs[len(calls) - 1]

    line = bench.run_config_isolated("3", _Args(), runner=runner)
    assert len(calls) == 2  # exactly one retry
    assert line["retried"] is True  # journaled evidence of the flake
    assert line["value"] == 1.0
    # the child must not double-journal
    # (parent sets BENCH_NO_JOURNAL=1 in the child env)


def test_bench_isolated_no_retry_for_other_failures():
    bench = _bench()
    calls = []

    def runner(cmd, **kw):
        calls.append(cmd)
        return _Proc(1, stderr="real failure")

    with pytest.raises(RuntimeError, match="rc=1"):
        bench.run_config_isolated("3", _Args(), runner=runner)
    assert len(calls) == 1  # no retry for non-rendezvous failures


def test_bench_isolated_second_rendezvous_death_raises():
    bench = _bench()
    calls = []

    def runner(cmd, **kw):
        calls.append(cmd)
        return _Proc(-6, stderr=_RENDEZVOUS_STDERR)

    with pytest.raises(RuntimeError, match="after one rendezvous retry"):
        bench.run_config_isolated("3", _Args(), runner=runner)
    assert len(calls) == 2  # retried once, then gave up


def test_bench_isolated_success_has_no_retried_flag():
    bench = _bench()
    good = json.dumps({"metric": "m", "value": 2.0, "unit": "s"})

    line = bench.run_config_isolated(
        "3", _Args(), runner=lambda cmd, **kw: _Proc(0, stdout=good)
    )
    assert "retried" not in line
