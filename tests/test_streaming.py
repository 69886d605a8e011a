"""Streaming engine tests — the StreamTest/MemoryStream analog (SURVEY.md §4
item 4): deterministic stepping, stop/restart with the same checkpoint dir,
exactly-once delivery, crash-after-intent replay."""

import json
import os

import numpy as np
import pytest

from sntc_tpu.core.frame import Frame
from sntc_tpu.data import generate_frame, write_day_csvs
from sntc_tpu.models import LogisticRegression
from sntc_tpu.serve import (
    BatchPredictor,
    CsvDirSink,
    FileStreamSource,
    MemorySink,
    MemorySource,
    StreamingQuery,
)


@pytest.fixture(scope="module")
def model(mesh8):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(800, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    return LogisticRegression(mesh=mesh8, maxIter=30).fit(
        Frame({"features": X, "label": y})
    )


def _batch(n, seed):
    rng = np.random.default_rng(seed)
    return Frame({"features": rng.normal(size=(n, 4)).astype(np.float32)})


def test_batch_predictor_chunks(model):
    f = _batch(1000, 1)
    out = BatchPredictor(model, chunk_rows=128).predict_frame(f)
    ref = model.transform(f)
    np.testing.assert_array_equal(out["prediction"], ref["prediction"])
    # arrow roundtrip path
    table = BatchPredictor(model).predict_batch(f.to_arrow())
    assert "prediction" in table.column_names


def test_streaming_processes_available_batches(model, tmp_path):
    src = MemorySource([_batch(50, 1), _batch(60, 2)])
    sink = MemorySink()
    q = StreamingQuery(model, src, sink, str(tmp_path / "ckpt"))
    assert q.process_available() == 1  # both frames drained in one batch
    assert sink.frames[0].num_rows == 110
    # new data arrives -> next batch only covers the delta
    src.add(_batch(30, 3))
    assert q.process_available() == 1
    assert sink.frames[1].num_rows == 30
    assert q.process_available() == 0


def test_streaming_resume_no_duplicates(model, tmp_path):
    ckpt = str(tmp_path / "ckpt")
    src = MemorySource([_batch(40, 1)])
    sink1 = MemorySink()
    q1 = StreamingQuery(model, src, sink1, ckpt)
    q1.process_available()
    q1.stop()

    # restart with same checkpoint: already-committed data is NOT reprocessed
    sink2 = MemorySink()
    q2 = StreamingQuery(model, src, sink2, ckpt)
    assert q2.process_available() == 0
    src.add(_batch(25, 2))
    assert q2.process_available() == 1
    assert [f.num_rows for f in sink2.frames] == [25]


def test_streaming_crash_after_intent_replays_exact_range(model, tmp_path):
    """Intent logged but uncommitted (crash between WAL and commit) -> the
    restarted query replays EXACTLY the logged range, even though more data
    arrived meanwhile (Spark's OffsetSeqLog recovery contract)."""
    ckpt = str(tmp_path / "ckpt")
    src = MemorySource([_batch(10, 1), _batch(20, 2)])
    os.makedirs(os.path.join(ckpt, "offsets"))
    os.makedirs(os.path.join(ckpt, "commits"))
    with open(os.path.join(ckpt, "offsets", "0.json"), "w") as f:
        json.dump({"batch_id": 0, "start": 0, "end": 1}, f)
    src.add(_batch(30, 3))  # late arrival

    sink = MemorySink()
    q = StreamingQuery(model, src, sink, ckpt)
    assert q.process_available() == 2
    # batch 0 replayed with the OLD range (first frame only), batch 1 gets the rest
    assert [f.num_rows for f in sink.frames] == [10, 50]


def test_streaming_max_batch_offsets(model, tmp_path):
    src = MemorySource([_batch(5, i) for i in range(4)])
    sink = MemorySink()
    q = StreamingQuery(
        model, src, sink, str(tmp_path / "ckpt"), max_batch_offsets=1
    )
    assert q.process_available() == 4  # one source offset per micro-batch
    assert [f.num_rows for f in sink.frames] == [5, 5, 5, 5]


def test_file_source_and_csv_sink(model, tmp_path, mesh8):
    """End-to-end config-5: CSV files stream in, predictions stream out,
    with offset/commit resume across query restarts [B:11]."""
    from sntc_tpu.data import CICIDS2017_FEATURES, clean_flows
    from sntc_tpu.core.base import Pipeline
    from sntc_tpu.feature import StandardScaler, StringIndexer, VectorAssembler

    train = clean_flows(generate_frame(3000, seed=5))
    train = train.with_column(
        "binLabel",
        np.where(train["Label"].astype(str) == "BENIGN", "benign", "attack").astype(object),
    )
    pipe_model = Pipeline(stages=[
        StringIndexer(inputCol="binLabel", outputCol="label"),
        VectorAssembler(inputCols=CICIDS2017_FEATURES, outputCol="features",
                        handleInvalid="skip"),
        LogisticRegression(mesh=mesh8, maxIter=30),
    ]).fit(train)
    # serving pipeline: drop the indexer (no labels on live flows)
    from sntc_tpu.core.base import PipelineModel
    serve_model = PipelineModel(stages=pipe_model.getStages()[1:])

    in_dir, out_dir = str(tmp_path / "in"), str(tmp_path / "out")
    write_day_csvs(in_dir, n_rows_per_day=40, n_days=2, seed=6)
    q = StreamingQuery(
        serve_model,
        FileStreamSource(in_dir),
        CsvDirSink(out_dir, columns=["prediction"]),
        str(tmp_path / "ckpt"),
    )
    assert q.process_available() == 1
    # two more day files land -> one more batch after "restart"
    write_day_csvs(in_dir, n_rows_per_day=40, n_days=4, seed=6)
    q2 = StreamingQuery(
        serve_model, FileStreamSource(in_dir),
        CsvDirSink(out_dir, columns=["prediction"]), str(tmp_path / "ckpt"),
    )
    assert q2.process_available() == 1
    outs = sorted(os.listdir(out_dir))
    assert outs == ["batch_000000.csv", "batch_000001.csv"]


# ---------------------------------------------------------------------------
# pipelined (async-dispatch) engine — config 5
# ---------------------------------------------------------------------------


def test_transform_async_matches_transform(model):
    f = _batch(500, 7)
    ref = model.transform(f)
    out = model.transform_async(f)()
    for col in ("rawPrediction", "probability", "prediction"):
        np.testing.assert_allclose(out[col], ref[col], rtol=1e-6)
    assert out["prediction"].dtype == ref["prediction"].dtype


def test_transform_async_honors_threshold_and_thresholds(model):
    f = _batch(400, 8)
    for params in ({"threshold": 0.9}, {"thresholds": [0.7, 0.3]}):
        m = model.copy(params)
        np.testing.assert_array_equal(
            m.transform_async(f)()["prediction"],
            m.transform(f)["prediction"],
        )


def test_pipelined_query_matches_depth1(model, tmp_path):
    batches = [_batch(40, s) for s in range(6)]
    outs = {}
    for depth in (1, 3):
        src = MemorySource(batches)
        sink = MemorySink()
        q = StreamingQuery(
            model, src, sink, str(tmp_path / f"ckpt_d{depth}"),
            max_batch_offsets=1, pipeline_depth=depth,
        )
        assert q.process_available() == 6
        outs[depth] = sink
    for (i1, f1), (i3, f3) in zip(outs[1].batches, outs[3].batches):
        assert i1 == i3
        np.testing.assert_array_equal(f1["prediction"], f3["prediction"])


def test_pipelined_crash_replays_inflight_intents(model, tmp_path):
    """A crash with several WAL'd-but-uncommitted intents must replay them
    with their logged ranges on restart (exactly-once, depth > 1)."""
    ckpt = str(tmp_path / "ckpt_crash")
    batches = [_batch(40, s) for s in range(5)]
    src = MemorySource(batches)
    sink = MemorySink()
    q = StreamingQuery(model, src, sink, ckpt, max_batch_offsets=1,
                       pipeline_depth=3)
    # dispatch 3 intents, commit only the first, then "crash"
    assert q._run_one_batch()
    assert q.last_committed() == 0
    assert len(q._in_flight) == 2
    pending = [t[1] for t in q._in_flight]
    del q  # crash: in-flight batches lost, intents remain in the WAL

    sink2 = MemorySink()
    q2 = StreamingQuery(model, src, sink2, ckpt, max_batch_offsets=1,
                        pipeline_depth=3)
    assert q2.last_committed() == 0
    assert q2.process_available() == 4  # replays 2 intents + 2 fresh
    committed = sorted(
        int(os.path.splitext(p)[0])
        for p in os.listdir(os.path.join(ckpt, "commits"))
    )
    assert committed == [0, 1, 2, 3, 4]
    # the replayed batches used the crashed run's logged ranges
    with open(os.path.join(ckpt, "commits", "1.json")) as f:
        assert json.load(f) == pending[0]
    with open(os.path.join(ckpt, "commits", "2.json")) as f:
        assert json.load(f) == pending[1]
    # every source batch delivered exactly once, in order
    assert [f.num_rows for f in sink2.frames] == [40, 40, 40, 40]


def test_pipelined_sink_failure_retries_not_skips(model, tmp_path):
    """A transient sink failure must leave the batch queued for retry —
    not skip it and shift later batch ids (exactly-once under depth>1)."""
    batches = [_batch(30, s) for s in range(4)]
    src = MemorySource(batches)

    class FlakySink(MemorySink):
        def __init__(self):
            super().__init__()
            self.fail_on = {1}

        def add_batch(self, batch_id, frame):
            if batch_id in self.fail_on:
                self.fail_on.discard(batch_id)
                raise IOError("transient sink outage")
            super().add_batch(batch_id, frame)

    sink = FlakySink()
    q = StreamingQuery(model, src, sink, str(tmp_path / "ckpt_flaky"),
                       max_batch_offsets=1, pipeline_depth=2)
    with pytest.raises(IOError):
        q.process_available()
    # retry drains the rest, including the failed batch, in order
    assert q.process_available() == 3
    assert [i for i, _ in sink.batches] == [0, 1, 2, 3]
    assert q.last_committed() == 3


def test_crash_between_sink_and_commit_replays_and_sink_dedupes(
    model, tmp_path
):
    """Crash injected at ``stream.commit`` (post-sink, pre-commit): the
    batch's output reached the sink but no commit landed.  On restart
    the batch is REPLAYED with its WAL-logged range and the CSV sink
    dedupes by rewriting ``batch_<id>.csv`` in place — row counts stay
    exactly-once, never doubled."""
    import sntc_tpu.resilience as R

    ckpt, out = str(tmp_path / "ckpt"), str(tmp_path / "out")
    src = MemorySource([_batch(40, 1), _batch(25, 2)])
    q = StreamingQuery(
        model, src, CsvDirSink(out, columns=["prediction"]), ckpt,
        max_batch_offsets=1,
    )
    R.arm("stream.commit", times=1)
    try:
        with pytest.raises(R.InjectedFault):
            q.process_available()
    finally:
        R.clear()
    # the sink saw batch 0; the offset log did not
    assert os.path.exists(os.path.join(out, "batch_000000.csv"))
    assert os.listdir(os.path.join(ckpt, "commits")) == []
    del q  # crash

    q2 = StreamingQuery(
        model, src, CsvDirSink(out, columns=["prediction"]), ckpt,
        max_batch_offsets=1,
    )
    assert q2.process_available() == 2  # batch 0 replayed + batch 1
    assert sorted(os.listdir(out)) == [
        "batch_000000.csv", "batch_000001.csv"
    ]
    with open(os.path.join(out, "batch_000000.csv")) as f:
        assert sum(1 for _ in f) - 1 == 40  # replayed rows, not doubled
    with open(os.path.join(ckpt, "commits", "0.json")) as f:
        assert json.load(f) == {"batch_id": 0, "start": 0, "end": 1}


def test_append_wal_resume_and_replay(model, tmp_path):
    """wal_mode='append': same exactly-once recovery contract as the
    per-file WAL — committed batches don't reprocess; a crash between
    intent and commit replays exactly the logged range."""
    ckpt = str(tmp_path / "ckpt")
    src = MemorySource([_batch(40, 1)])
    sink1 = MemorySink()
    q1 = StreamingQuery(model, src, sink1, ckpt, wal_mode="append")
    assert q1.process_available() == 1
    q1.stop()

    sink2 = MemorySink()
    q2 = StreamingQuery(model, src, sink2, ckpt, wal_mode="append")
    assert q2.process_available() == 0  # committed data not reprocessed
    src.add(_batch(25, 2))
    assert q2.process_available() == 1
    assert [f.num_rows for f in sink2.frames] == [25]
    q2.stop()

    # crash-after-intent: hand-write an uncommitted intent line
    ckpt2 = str(tmp_path / "ckpt2")
    os.makedirs(ckpt2)
    with open(os.path.join(ckpt2, "offsets.log"), "w") as f:
        f.write(json.dumps({"batch_id": 0, "start": 0, "end": 1}) + "\n")
    src3 = MemorySource([_batch(10, 1), _batch(20, 2)])
    sink3 = MemorySink()
    q3 = StreamingQuery(model, src3, sink3, ckpt2, wal_mode="append",
                        )
    assert q3.process_available() == 2
    assert [f.num_rows for f in sink3.frames] == [10, 20]


def test_append_wal_rejects_files_mode_dir(model, tmp_path):
    ckpt = str(tmp_path / "ckpt")
    src = MemorySource([_batch(10, 1)])
    q = StreamingQuery(model, src, MemorySink(), ckpt)  # files mode
    q.process_available()
    q.stop()
    with pytest.raises(ValueError, match="files"):
        StreamingQuery(model, src, MemorySink(), ckpt, wal_mode="append")


def test_recent_progress_records(model, tmp_path):
    src = MemorySource([_batch(5, i) for i in range(3)])
    sink = MemorySink()
    q = StreamingQuery(model, src, sink, str(tmp_path / "ckpt"),
                       max_batch_offsets=1)
    q.process_available()
    assert [p["batchId"] for p in q.recentProgress] == [0, 1, 2]
    for p in q.recentProgress:
        assert p["numInputRows"] == 5
        assert p["durationMs"] > 0
        assert p["processedRowsPerSecond"] > 0


def test_start_await_termination_lifecycle(model, tmp_path):
    """writeStream.start() analog: background loop drains arriving data;
    stop() joins the thread; lastProgress/isActive surface state."""
    import time as _time

    src = MemorySource([_batch(20, 1)])
    sink = MemorySink()
    q = StreamingQuery(model, src, sink, str(tmp_path / "ckpt"),
                       max_batch_offsets=1)
    q.start(poll_interval=0.02)
    assert q.isActive
    deadline = _time.time() + 30
    while _time.time() < deadline and len(sink.frames) < 1:
        _time.sleep(0.02)
    src.add(_batch(10, 2))  # arrives while running
    while _time.time() < deadline and len(sink.frames) < 2:
        _time.sleep(0.02)
    assert [f.num_rows for f in sink.frames] == [20, 10]
    assert not q.awaitTermination(timeout=0.05)  # still polling
    assert q.lastProgress["numInputRows"] == 10
    q.stop()
    assert not q.isActive
    assert q.awaitTermination(timeout=1.0)
    with pytest.raises(RuntimeError, match="stopped"):
        q.start()


def test_await_termination_reraises_loop_crash(model, tmp_path):
    class BoomSink(MemorySink):
        def add_batch(self, batch_id, frame):
            raise RuntimeError("sink boom")

    src = MemorySource([_batch(10, 1)])
    q = StreamingQuery(model, src, BoomSink(), str(tmp_path / "ckpt"))
    q.start(poll_interval=0.02)
    with pytest.raises(RuntimeError, match="sink boom"):
        q.awaitTermination(timeout=30)


# ---------------------------------------------------------------------------
# r8: shape-bucketed predict — padded+masked batches are bitwise-equal
# to unpadded ones, and the compile ledger stays flat after warmup
# ---------------------------------------------------------------------------


def _family_models(mesh8):
    """One fitted model per family the predictor serves (small fits)."""
    from sntc_tpu.models import (
        LinearSVC,
        LogisticRegression,
        MultilayerPerceptronClassifier,
        NaiveBayes,
        RandomForestClassifier,
    )

    rng = np.random.default_rng(3)
    X = rng.normal(size=(240, 4)).astype(np.float32)
    y3 = (np.abs(X[:, 0]) + X[:, 1] > 0.8).astype(np.float64) + (
        X[:, 2] > 0.5
    ).astype(np.float64)
    train3 = Frame({"features": X, "label": y3})
    ybin = (X[:, 0] > 0).astype(np.float64)
    train2 = Frame({"features": X, "label": ybin})
    # tiny fits: bucket correctness is about transform row-locality, not
    # model quality — keep the tier-1 bill small
    return {
        "lr": LogisticRegression(mesh=mesh8, maxIter=8).fit(train2),
        "mlp": MultilayerPerceptronClassifier(
            mesh=mesh8, layers=[4, 8, 3], maxIter=8, seed=0
        ).fit(train3),
        "rf": RandomForestClassifier(
            mesh=mesh8, numTrees=3, maxDepth=3, seed=0
        ).fit(train3),
        "nb": NaiveBayes(mesh=mesh8, modelType="gaussian").fit(train3),
        "svc": LinearSVC(mesh=mesh8, maxIter=8).fit(train2),
    }


def test_bucketed_predict_bitwise_equal_across_families(mesh8):
    """Satellite: padded+masked predictions == unpadded predictions for
    every model family, and compile_events stays flat after the bucket
    shapes are warm (varying batch sizes, same buckets)."""
    sizes_warm = (50, 100)  # buckets 64 and 128
    sizes_after = (49, 60, 63, 90, 127, 100)  # same two buckets
    for name, m in _family_models(mesh8).items():
        bp = BatchPredictor(m, bucket_rows=16)
        for n in sizes_warm:
            bp.predict_frame(_batch(n, n))
        warm_events = bp.compile_events
        assert warm_events == 2, (name, bp.compile_events)
        for n in sizes_after:
            f = _batch(n, n)
            out = bp.predict_frame(f)
            ref = m.transform(f)
            assert out.num_rows == n, name
            assert out.columns == ref.columns, name
            np.testing.assert_array_equal(
                out["prediction"], ref["prediction"], err_msg=name
            )
            if "probability" in ref:  # LinearSVC emits margins only
                np.testing.assert_allclose(
                    out["probability"], ref["probability"], rtol=1e-6,
                    err_msg=name,
                )
        assert bp.compile_events == warm_events, name  # zero recompiles
        assert bp.bucket_hits >= len(sizes_after), name
        assert bp.padded_rows_total > 0, name


def test_bucketed_predict_threads_mask_through_row_dropping_stage(mesh8):
    """The row-validity mask survives a row-DROPPING stage: a pipeline
    whose assembler skips invalid rows must yield exactly the surviving
    real rows — tail-slicing would return the wrong rows here."""
    from sntc_tpu.core.base import PipelineModel
    from sntc_tpu.feature import VectorAssembler
    from sntc_tpu.models import LogisticRegression

    rng = np.random.default_rng(5)
    cols = {f"c{i}": rng.normal(size=300).astype(np.float32)
            for i in range(4)}
    train = Frame(dict(cols))
    train = train.with_column(
        "label", (train["c0"] > 0).astype(np.float64)
    )
    asm = VectorAssembler(
        inputCols=[f"c{i}" for i in range(4)], outputCol="features",
        handleInvalid="skip",
    )
    lr = LogisticRegression(mesh=mesh8, maxIter=10).fit(
        asm.transform(train)
    )
    pipe = PipelineModel(stages=[asm, lr])

    bad = {f"c{i}": rng.normal(size=70).astype(np.float32)
           for i in range(4)}
    bad["c1"] = bad["c1"].copy()
    bad["c1"][[3, 11, 42]] = np.nan  # 3 real rows get skipped
    f = Frame(bad)
    ref = pipe.transform(f)
    assert ref.num_rows == 67
    out = BatchPredictor(pipe, bucket_rows=64).predict_frame(f)
    assert out.num_rows == 67
    np.testing.assert_array_equal(out["prediction"], ref["prediction"])
    np.testing.assert_array_equal(out["c0"], ref["c0"])


def test_oversized_frame_chunked_async_dispatch(model):
    """predict_frame_async over a frame larger than chunk_rows: all
    chunks dispatch before finalize, one finalize concatenates, results
    match the one-shot transform (bucketed tail chunk included)."""
    f = _batch(1000, 9)
    ref = model.transform(f)
    for bucket in (0, 64):
        bp = BatchPredictor(model, chunk_rows=256, bucket_rows=bucket)
        fin = bp.predict_frame_async(f)
        out = fin()
        assert out.num_rows == 1000
        np.testing.assert_array_equal(out["prediction"], ref["prediction"])
        np.testing.assert_allclose(
            out["probability"], ref["probability"], rtol=1e-6
        )
    # bucketed: 3 full 256-row chunks share one shape, the 232-row tail
    # pads into the same 256 bucket — ONE compile event total
    assert bp.compile_events == 1


# ---------------------------------------------------------------------------
# r8: pipelined engine — prefetching source + overlapped sink delivery
# ---------------------------------------------------------------------------


def _write_stream(tmp_path, n_files=6, rows=30):
    from sntc_tpu.data import write_day_csvs

    in_dir = str(tmp_path / "in")
    write_day_csvs(in_dir, n_rows_per_day=rows, n_days=n_files, seed=4)
    return in_dir


def test_single_listing_serves_latest_offset_and_get_batch(
    tmp_path, monkeypatch
):
    """Satellite: one glob+sort per poll tick — latest_offset() caches
    the listing and the tick's get_batch() reuses it."""
    import sntc_tpu.serve.streaming as S

    in_dir = _write_stream(tmp_path, n_files=3)
    src = FileStreamSource(in_dir)
    calls = {"n": 0}
    real_glob = S.glob.glob

    def counting(*a, **k):
        calls["n"] += 1
        return real_glob(*a, **k)

    monkeypatch.setattr(S.glob, "glob", counting)
    off = src.latest_offset()
    assert off == 3 and calls["n"] == 1
    f = src.get_batch(0, off)
    assert f.num_rows == 90
    assert calls["n"] == 1  # reused the tick's listing
    # a range past the cached listing re-scans
    with pytest.raises(ValueError):
        src.get_batch(3, 5)
    assert calls["n"] == 2


def test_prefetch_stages_next_batch(tmp_path):
    """prefetch(start, end) stages a background read; get_batch with
    that exact range consumes it, other ranges fall through."""
    in_dir = _write_stream(tmp_path, n_files=4)
    src = FileStreamSource(in_dir, prefetch_batches=1)
    assert src.latest_offset() == 4
    assert src.prefetch(0, 1)
    assert not src.prefetch(0, 1)  # already staged
    assert not src.prefetch(0, 2)  # queue full (bound = 1)
    f = src.get_batch(0, 1)  # consumes the staged read
    assert f.num_rows == 30
    assert src.prefetch(1, 2)  # slot free again
    # a shed that skipped past offset 2 evicts the now-stale (1, 2)
    assert src.prefetch(2, 4)
    assert (1, 2) not in src._staged
    f2 = src.get_batch(2, 4)
    assert f2.num_rows == 60
    stats = src.prefetch_stats()
    assert stats["hits"] == 2 and stats["hwm"] == 1
    # staged contents identical to a cold synchronous read
    ref = FileStreamSource(in_dir).get_batch(0, 1)
    np.testing.assert_array_equal(ref["Flow Duration"], f["Flow Duration"])
    src.close()


@pytest.mark.parametrize("wal_mode", ["files", "append"])
def test_overlap_sink_query_matches_serial(model, tmp_path, wal_mode):
    """The full pipelined engine (overlap + prefetch + buckets) commits
    the same batches with the same contents as the serial engine."""
    batches = [_batch(40 + 11 * i, i) for i in range(6)]
    outs = {}
    for mode in ("serial", "pipe"):
        src = MemorySource(batches)
        sink = MemorySink()
        q = StreamingQuery(
            model, src, sink, str(tmp_path / f"ckpt_{wal_mode}_{mode}"),
            max_batch_offsets=1, wal_mode=wal_mode,
            pipeline_depth=1 if mode == "serial" else 3,
            overlap_sink=mode == "pipe",
            shape_buckets=0 if mode == "serial" else 32,
        )
        assert q.process_available() == 6
        assert q.in_flight_count() == 0
        assert q._delivery is None
        q.stop()
        outs[mode] = sink
    for (i1, f1), (i2, f2) in zip(
        outs["serial"].batches, outs["pipe"].batches
    ):
        assert i1 == i2
        assert f1.num_rows == f2.num_rows
        np.testing.assert_array_equal(f1["prediction"], f2["prediction"])


def test_overlap_sink_file_source_end_to_end(model, tmp_path):
    """Pipelined engine over a real prefetching file source and CSV
    sink: exactly-once output files, prefetch hits recorded."""
    from sntc_tpu.data import CICIDS2017_FEATURES  # noqa: F401 — schema sanity

    in_dir = _write_stream(tmp_path, n_files=5)
    src = FileStreamSource(in_dir, prefetch_batches=2)

    class Echo(MemorySink):
        pass

    sink = Echo()

    from sntc_tpu.core.base import Transformer

    class Identity(Transformer):
        def transform(self, frame):
            return frame

    q = StreamingQuery(
        Identity(), src, sink, str(tmp_path / "ckpt"),
        max_batch_offsets=1, pipeline_depth=3, overlap_sink=True,
        shape_buckets=16,
    )
    assert q.process_available() == 5
    q.stop()
    assert [i for i, _ in sink.batches] == [0, 1, 2, 3, 4]
    assert all(f.num_rows == 30 for f in sink.frames)
    stats = q.pipeline_stats()
    assert stats["prefetch"]["hits"] >= 1
    assert stats["delivered_batches"] == 5
    src.close()


def test_overlap_sink_failure_defers_not_skips(model, tmp_path):
    """Serial-contract parity under overlap: a transient sink failure
    leaves the batch queued (ids never shift); unarmed quarantine
    re-raises from process_available."""
    batches = [_batch(30, s) for s in range(4)]
    src = MemorySource(batches)

    class FlakySink(MemorySink):
        def __init__(self):
            super().__init__()
            self.fail_on = {1}

        def add_batch(self, batch_id, frame):
            if batch_id in self.fail_on:
                self.fail_on.discard(batch_id)
                raise IOError("transient sink outage")
            super().add_batch(batch_id, frame)

    sink = FlakySink()
    q = StreamingQuery(model, src, sink, str(tmp_path / "ckpt_flaky"),
                       max_batch_offsets=1, pipeline_depth=2,
                       overlap_sink=True)
    with pytest.raises(IOError):
        q.process_available()
    assert q.process_available() == 3
    assert [i for i, _ in sink.batches] == [0, 1, 2, 3]
    assert q.last_committed() == 3
    q.stop()


def test_overlap_crash_between_sink_and_commit_replays(model, tmp_path):
    """stream.commit crash in overlap mode: the delivery reached the
    sink, the commit never landed; a restarted (pipelined) query
    replays the batch and the sink dedupes — exactly-once preserved."""
    import sntc_tpu.resilience as R

    ckpt, out = str(tmp_path / "ckpt"), str(tmp_path / "out")
    src = MemorySource([_batch(40, 1), _batch(25, 2)])
    q = StreamingQuery(
        model, src, CsvDirSink(out, columns=["prediction"]), ckpt,
        max_batch_offsets=1, pipeline_depth=2, overlap_sink=True,
    )
    R.arm("stream.commit", times=1)
    try:
        with pytest.raises(R.InjectedFault):
            q.process_available()
    finally:
        R.clear()
    assert os.path.exists(os.path.join(out, "batch_000000.csv"))
    assert os.listdir(os.path.join(ckpt, "commits")) == []
    q.stop()
    del q  # crash

    q2 = StreamingQuery(
        model, src, CsvDirSink(out, columns=["prediction"]), ckpt,
        max_batch_offsets=1, pipeline_depth=2, overlap_sink=True,
    )
    assert q2.process_available() == 2
    q2.stop()
    with open(os.path.join(out, "batch_000000.csv")) as f:
        assert sum(1 for _ in f) - 1 == 40  # replayed, not doubled
    with open(os.path.join(ckpt, "commits", "0.json")) as f:
        assert json.load(f) == {"batch_id": 0, "start": 0, "end": 1}


def test_overlap_drain_settles_in_air_delivery(model, tmp_path):
    """drain() in overlap mode joins the delivery thread's in-air batch
    and commits everything in flight — the preemption contract."""
    import time as _time

    class SlowSink(MemorySink):
        def add_batch(self, batch_id, frame):
            _time.sleep(0.05)
            super().add_batch(batch_id, frame)

    batches = [_batch(20, s) for s in range(4)]
    sink = SlowSink()
    q = StreamingQuery(
        model, MemorySource(batches), sink,
        str(tmp_path / "ckpt"), max_batch_offsets=1, pipeline_depth=3,
        overlap_sink=True,
    )
    # fill the pipeline and put one delivery in the air, then drain
    q._run_one_batch()
    assert q.in_flight_count() >= 1
    q.drain()
    assert q.in_flight_count() == 0
    assert q._delivery is None
    # every dispatched batch was sunk exactly once, in order, and the
    # commit log agrees with the sink
    ids = [i for i, _ in sink.batches]
    assert ids == list(range(len(ids))) and len(ids) >= 1
    assert q.last_committed() == ids[-1]
    q.stop()


def test_perf_flags_drift_check():
    """CLI flags ⇔ engine kwargs ⇔ docs must agree (tier-1 wiring of
    scripts/check_perf_flags.py)."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "check_perf_flags",
        os.path.join(repo, "scripts", "check_perf_flags.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.check() == []


def test_hot_swap_never_lands_mid_delivery(tmp_path):
    """r11 hot-swap safety under overlap_sink: ``swap_model`` settles
    the in-air delivery FIRST (the head batch commits under the old
    generation on this thread) and only then flips the predictor — a
    swap can never land while a delivery is in the air."""
    import threading

    import numpy as np

    from sntc_tpu.models.logistic_regression import (
        LogisticRegressionModel,
    )

    def const_model(positive):
        # zero coefficients + a pinned intercept: predicts ONE class
        # everywhere, so the sink rows prove which model served them
        return LogisticRegressionModel(
            coefficient_matrix=np.zeros((2, 4), np.float32),
            intercepts=np.asarray(
                [0.0, 50.0 if positive else -50.0], np.float32
            ),
            is_binomial=True,
        )

    incumbent, candidate = const_model(False), const_model(True)
    entered, release = threading.Event(), threading.Event()
    holder = {}
    events = []

    class GatedSink(MemorySink):
        def add_batch(self, batch_id, frame):
            entered.set()
            assert release.wait(timeout=10), "swap should release us"
            # the engine predictor must still wrap the OLD model while
            # this delivery is in the air — the swap waits for us
            events.append(
                ("sunk", batch_id,
                 holder["q"].predictor.model is incumbent)
            )
            super().add_batch(batch_id, frame)

    sink = GatedSink()
    src = MemorySource([_batch(20, s) for s in range(2)])
    q = StreamingQuery(
        incumbent, src, sink, str(tmp_path / "ckpt"),
        max_batch_offsets=1, pipeline_depth=2, overlap_sink=True,
    )
    holder["q"] = q
    q._run_one_batch()  # batch 0's delivery is now in the air
    assert entered.wait(timeout=10)
    assert q._delivery is not None
    # release the gated sink shortly AFTER swap_model starts waiting on
    # the in-air head; the swap must join it, not overtake it
    threading.Timer(0.2, release.set).start()
    old = q.swap_model(candidate)
    events.append(("swapped",))
    assert old is incumbent
    assert q._delivery is None  # the head settled before the flip
    assert q.models_swapped == 1
    # ordering evidence: the in-air delivery completed (under the old
    # model) strictly before the swap was applied
    assert events[0] == ("sunk", 0, True)
    assert events[-1] == ("swapped",)
    # drain the rest: batches dispatched after the swap serve class 1
    src.add(_batch(15, 9))
    q.process_available()
    q.stop()
    first = np.asarray(sink.frames[0]["prediction"])
    last = np.asarray(sink.frames[-1]["prediction"])
    np.testing.assert_array_equal(first, np.zeros_like(first))
    np.testing.assert_array_equal(last, np.ones_like(last))
    assert q.last_committed() == len(sink.frames) - 1


# ---------------------------------------------------------------------------
# concurrent engines on ONE shared BatchPredictor (r12): the serve
# daemon hands tenants sharing a pipeline one predictor, so two engines
# dispatching through it from separate threads must (a) produce the
# exact sink output a serial run produces and (b) never widen the
# shared compile ledger past the union of their bucket shapes — the
# thread-safety contract the daemon's shared program cache depends on
# ---------------------------------------------------------------------------


def test_concurrent_queries_shared_predictor_bitwise_vs_serial(
    mesh8, tmp_path, monkeypatch
):
    import threading

    from sntc_tpu.core.base import Pipeline
    from sntc_tpu.feature import MinMaxScaler, VectorAssembler
    from sntc_tpu.fuse import compile_pipeline, fused_segments

    # fused serving always runs on device; pin the staged host-serve
    # crossover off so serial and concurrent hit one numerical path
    monkeypatch.setenv("SNTC_SERVE_HOST_ROWS", "0")

    def scalar_frame(n, seed):
        rng = np.random.default_rng(seed)
        cols = {
            f"c{i}": rng.normal(3.0, 2.0, size=n).astype(np.float32)
            for i in range(4)
        }
        return Frame(cols)

    train = scalar_frame(400, 99)
    train = Frame(
        {
            **{c: train[c] for c in train.columns},
            "label": (np.asarray(train["c0"]) > 3.0).astype(np.float64),
        }
    )
    pm = Pipeline(stages=[
        VectorAssembler(inputCols=[f"c{i}" for i in range(4)],
                        outputCol="features"),
        MinMaxScaler(inputCol="features", outputCol="scaled"),
        LogisticRegression(mesh=mesh8, featuresCol="scaled", maxIter=20),
    ]).fit(train)
    fused = compile_pipeline(pm)
    assert fused_segments(fused), "pipeline should fuse"

    # per-tenant streams with DIFFERENT row counts that land in two
    # buckets (5,7 -> 8; 11,13 -> 16): the shared ledger must hold
    # exactly those two shapes however the threads interleave
    frames = {
        "a": [scalar_frame(5, 10 + i) for i in range(4)]
        + [scalar_frame(11, 20 + i) for i in range(4)],
        "b": [scalar_frame(7, 30 + i) for i in range(4)]
        + [scalar_frame(13, 40 + i) for i in range(4)],
    }

    def run(pred, tid, ckpt_tag):
        sink = MemorySink()
        q = StreamingQuery(
            pred, MemorySource(frames[tid]), sink,
            str(tmp_path / f"{ckpt_tag}-{tid}"), max_batch_offsets=1,
        )
        q.process_available()
        q.stop()
        return sink

    # serial reference: each tenant alone on its OWN predictor
    serial = {
        tid: run(BatchPredictor(fused, bucket_rows=8), tid, "serial")
        for tid in ("a", "b")
    }

    shared = BatchPredictor(fused, bucket_rows=8)
    results, errs = {}, []

    def worker(tid):
        try:
            results[tid] = run(shared, tid, "conc")
        except Exception as e:  # pragma: no cover - failure evidence
            errs.append(e)

    threads = [
        threading.Thread(target=worker, args=(tid,)) for tid in ("a", "b")
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs

    # (a) bitwise: every tenant's concurrent sink == its serial sink
    for tid in ("a", "b"):
        assert len(results[tid].frames) == len(serial[tid].frames)
        for got, want in zip(results[tid].frames, serial[tid].frames):
            assert got.num_rows == want.num_rows
            for col in ("rawPrediction", "probability", "prediction"):
                if col in want:
                    np.testing.assert_array_equal(
                        np.asarray(got[col]), np.asarray(want[col]),
                        err_msg=f"{tid}:{col}",
                    )

    # (b) flat shared ledger: exactly the two bucket shapes, however
    # the threads raced; every later dispatch was a bucket hit
    assert shared.compile_events == 2
    assert shared.bucket_hits == sum(len(v) for v in frames.values()) - 2
