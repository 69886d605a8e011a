"""Unified telemetry substrate (r13, ``sntc_tpu.obs``): metrics
registry semantics (labels, cardinality cap, histogram bucket edges,
snapshot under concurrent writes, exposition), span-tracer ring
behavior and Chrome-trace export, the event→metrics bridge, the
per-engine transfer-ledger attribution, the end-to-end agreement of
one serve run's Prometheus snapshot with the legacy ledger views, and
the metric-name drift check (tier-1 wiring of check_metric_names)."""

import importlib.util
import json
import os
import threading

import numpy as np
import pytest

import sntc_tpu.resilience as R
from sntc_tpu import obs
from sntc_tpu.core.base import Pipeline, Transformer
from sntc_tpu.core.frame import Frame
from sntc_tpu.feature import MinMaxScaler, VectorAssembler
from sntc_tpu.models import LogisticRegression
from sntc_tpu.obs import SpanTracer, disable_tracing, enable_tracing
from sntc_tpu.obs import span as obs_span
from sntc_tpu.obs import tracer as obs_tracer
from sntc_tpu.obs.bridge import split_tenant_site
from sntc_tpu.obs.metrics import CATALOG, MetricsRegistry, registry
from sntc_tpu.serve import (
    MemorySink,
    MemorySource,
    ServeDaemon,
    TenantSpec,
)
from sntc_tpu.utils.profiling import (
    TransferLedger,
    active_ledgers,
    ledger_scope,
    transfer_ledger,
)

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
    disable_tracing()


def _get(name, **labels):
    return registry().get(name, **labels) or 0


# ---------------------------------------------------------------------------
# MetricsRegistry unit semantics
# ---------------------------------------------------------------------------


def test_counter_gauge_and_labels():
    r = MetricsRegistry()
    r.inc("sntc_rows_committed_total", 5)
    r.inc("sntc_rows_committed_total", 2)
    r.inc("sntc_rows_committed_total", 3, tenant="a")
    r.set_gauge("sntc_health_state", 2, component="engine")
    r.set_gauge("sntc_health_state", 1, component="engine")
    assert r.get("sntc_rows_committed_total") == 7
    assert r.get("sntc_rows_committed_total", tenant="a") == 3
    assert r.get("sntc_health_state", component="engine") == 1
    assert r.get("sntc_rows_committed_total", tenant="nope") is None


def test_registry_rejects_undeclared_names_and_labels():
    r = MetricsRegistry()
    with pytest.raises(KeyError, match="CATALOG"):
        r.inc("sntc_made_up_total")
    with pytest.raises(KeyError, match="label"):
        r.inc("sntc_rows_committed_total", 1, flavor="x")
    with pytest.raises(KeyError, match="histogram"):
        r.observe("sntc_rows_committed_total", 1.0)


def test_label_cardinality_cap_collapses_to_overflow():
    r = MetricsRegistry(max_label_sets=4)
    for i in range(9):
        r.inc("sntc_rows_committed_total", 1, tenant=f"t{i}")
    # first 4 label sets kept; the 5 surplus collapse into overflow
    assert r.label_overflows() == 5
    assert r.get("sntc_rows_committed_total", overflow="true") == 5
    for i in range(4):
        assert r.get("sntc_rows_committed_total", tenant=f"t{i}") == 1
    snap = r.snapshot()["sntc_rows_committed_total"]
    assert len(snap["series"]) == 5  # 4 kept + overflow


def test_histogram_bucket_edges():
    spec = CATALOG["sntc_batch_duration_seconds"]
    bounds = spec["buckets"]
    r = MetricsRegistry()
    # exactly ON a bound counts into that bound's bucket (le semantics)
    r.observe("sntc_batch_duration_seconds", bounds[0])
    r.observe("sntc_batch_duration_seconds", bounds[0] * 1.0001)
    r.observe("sntc_batch_duration_seconds", 1e9)  # +Inf bucket
    s = r.snapshot()["sntc_batch_duration_seconds"]["series"][0]
    assert s["buckets"][0] == 1
    assert s["buckets"][1] == 1
    assert s["buckets"][-1] == 1
    assert s["count"] == 3
    text = r.to_prometheus()
    assert f'sntc_batch_duration_seconds_bucket{{le="{bounds[0]}"}} 1' in text
    # cumulative: the second bucket line includes the first's count
    assert f'sntc_batch_duration_seconds_bucket{{le="{bounds[1]}"}} 2' in text
    assert 'sntc_batch_duration_seconds_bucket{le="+Inf"} 3' in text
    assert "sntc_batch_duration_seconds_count 3" in text


def test_snapshot_under_concurrent_writes():
    r = MetricsRegistry()
    N_THREADS, N_INC = 8, 2_000
    stop = threading.Event()
    snaps = []

    def writer(i):
        for _ in range(N_INC):
            r.inc("sntc_rows_committed_total", 1, tenant=f"w{i % 3}")
            r.observe("sntc_batch_duration_seconds", 0.01)

    def reader():
        while not stop.is_set():
            snaps.append(r.snapshot())
            r.to_prometheus()

    threads = [
        threading.Thread(target=writer, args=(i,))
        for i in range(N_THREADS)
    ]
    rt = threading.Thread(target=reader)
    rt.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    rt.join()
    total = sum(
        r.get("sntc_rows_committed_total", tenant=f"w{k}")
        for k in range(3)
    )
    assert total == N_THREADS * N_INC  # no lost increments
    s = r.snapshot()["sntc_batch_duration_seconds"]["series"][0]
    assert s["count"] == N_THREADS * N_INC
    assert snaps, "reader never snapshotted"
    # monotone non-decreasing totals across the reader's snapshots
    last = -1
    for snap in snaps:
        rows = snap.get("sntc_rows_committed_total")
        tot = sum(x["value"] for x in rows["series"]) if rows else 0
        assert tot >= last
        last = tot


def test_jsonl_exposition_deterministic_clock(tmp_path):
    r = MetricsRegistry(clock=lambda: 123.5, mono=lambda: 7.25)
    r.inc("sntc_daemon_ticks_total", 3)
    path = str(tmp_path / "m.jsonl")
    rec = r.write_jsonl(path)
    assert (rec["ts"], rec["mono"], rec["seq"]) == (123.5, 7.25, 0)
    r.write_jsonl(path)
    with open(path) as f:
        lines = [json.loads(line) for line in f]
    assert [r_["seq"] for r_ in lines] == [0, 1]
    assert (
        lines[0]["metrics"]["sntc_daemon_ticks_total"]["series"][0][
            "value"
        ]
        == 3
    )


def test_write_prometheus_atomic(tmp_path):
    r = MetricsRegistry()
    r.inc("sntc_daemon_ticks_total")
    path = str(tmp_path / "m.prom")
    r.write_prometheus(path)
    with open(path) as f:
        text = f.read()
    assert "# TYPE sntc_daemon_ticks_total counter" in text
    assert "sntc_daemon_ticks_total 1" in text
    assert not os.path.exists(path + ".tmp")


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_span_ring_overflow_drops_oldest_and_counts():
    t = SpanTracer(capacity=4)
    for i in range(7):
        with t.span("s", i=i):
            pass
    spans = t.spans()
    assert len(spans) == 4
    assert [s["attrs"]["i"] for s in spans] == [3, 4, 5, 6]
    assert t.dropped == 3
    assert t.stats() == {"spans": 4, "capacity": 4, "dropped": 3}


def test_span_records_on_exception_and_clocks():
    base = {"t": 0.0}
    t = SpanTracer(clock=lambda: base["t"], wall=lambda: 1000 + base["t"])
    with pytest.raises(ValueError):
        with t.span("boom"):
            base["t"] = 2.5
            raise ValueError("x")
    (s,) = t.spans()
    assert s["name"] == "boom"
    assert s["dur_s"] == 2.5
    assert s["wall"] == 1000.0


def test_module_span_noop_when_disabled_records_when_enabled():
    assert obs_tracer() is None
    with obs_span("ignored", k=1):
        pass  # no tracer: shared null context
    t = enable_tracing(capacity=16)
    assert obs_tracer() is t
    with obs_span("live", k=2):
        pass
    assert [s["name"] for s in t.spans()] == ["live"]
    assert disable_tracing() is t
    with obs_span("ignored-again"):
        pass
    assert [s["name"] for s in t.spans()] == ["live"]


def test_chrome_trace_export_loadable(tmp_path):
    t = SpanTracer(capacity=16)
    with t.span("outer", batch=1):
        with t.span("inner"):
            pass
    path = t.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in events} == {"outer", "inner"}
    for e in events:
        assert e["dur"] >= 0 and "ts" in e and "tid" in e
        assert "wall_ts" in e["args"]
    assert events[1]["args"]["batch"] == 1  # ring order: inner first
    assert any(e["ph"] == "M" for e in doc["traceEvents"])  # thread names


# ---------------------------------------------------------------------------
# one span API, two sinks: the ring and the profiler's trace (one clock)
# ---------------------------------------------------------------------------


def _profiler_events(log_dir):
    """``{name: [(start_ns, end_ns, stats, line)]}`` of every host event in
    the newest ``.xplane.pb`` under ``log_dir``, read with jax alone."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append((
                    ev.start_ns, ev.start_ns + ev.duration_ns,
                    {str(k): v for k, v in ev.stats}, line.name,
                ))
    return out


def test_span_lands_in_profiler_trace_nested_with_stats(tmp_path):
    assert obs_tracer() is None  # the session alone is the switch
    with obs.device_trace(str(tmp_path)):
        with obs_span("outer.stage", rows=12, module="core"):
            with obs_span("inner.stage", stage="Indexer", index=0):
                pass
            with obs_span("marker", seconds=0.5, outcome="compiled"):
                pass
    events = _profiler_events(str(tmp_path))
    (outer,) = events["sntc:outer.stage"]
    (inner,) = events["sntc:inner.stage"]
    (marker,) = events["sntc:marker"]
    assert outer[2] == {"rows": 12, "module": "core"}
    assert inner[2] == {"stage": "Indexer", "index": 0}
    assert marker[2] == {"seconds": 0.5, "outcome": "compiled"}
    # one clock, one thread's line: children inside the parent's interval
    assert outer[3] == inner[3] == marker[3]
    assert outer[0] <= inner[0] <= inner[1] <= marker[0] <= marker[1]
    assert marker[1] <= outer[1]
    assert "outer.stage" not in events  # only under the sntc: prefix


def test_device_trace_writes_no_python_tracer_events(tmp_path):
    def traced_python_call():
        return sum(range(10))

    with obs.device_trace(str(tmp_path)):
        with obs_span("only.this"):
            traced_python_call()
    events = _profiler_events(str(tmp_path))
    assert "sntc:only.this" in events
    # the Python tracer names its events "$<file>:<line> <function>"
    assert not [n for n in events if n.startswith("$")]


def test_span_is_the_shared_null_with_no_session_and_no_ring(tmp_path):
    from sntc_tpu.obs import trace as obs_trace

    assert obs_span("nothing.on", k=1) is obs_trace._NULL_SPAN
    with obs.device_trace(str(tmp_path)):
        assert obs_span("session.on") is not obs_trace._NULL_SPAN
    # the session closed: the switch is off again
    assert obs_span("nothing.on") is obs_trace._NULL_SPAN
    with obs_span("nothing.on") as live:
        assert live is None


def test_span_goes_to_both_sinks_at_once(tmp_path):
    t = enable_tracing(capacity=16)
    with obs.device_trace(str(tmp_path)):
        with obs_span("both.sinks", batch=3):
            pass
    with obs_span("ring.only"):
        pass
    assert [s["name"] for s in t.spans()] == ["both.sinks", "ring.only"]
    assert t.spans()[0]["attrs"] == {"batch": 3}
    events = _profiler_events(str(tmp_path))
    assert events["sntc:both.sinks"][0][2] == {"batch": 3}
    assert "sntc:ring.only" not in events


def test_ring_records_carry_id_and_parent(tmp_path):
    t = SpanTracer(capacity=16)
    with t.span("root"):
        with t.span("child.a"):
            with t.span("leaf"):
                pass
        with t.span("child.b"):
            pass
    other = {}

    def elsewhere():
        with t.span("other.thread"):
            pass
        other["done"] = True

    with t.span("root.2"):
        th = threading.Thread(target=elsewhere)
        th.start()
        th.join(timeout=30)
    assert other == {"done": True}
    by_name = {s["name"]: s for s in t.spans()}
    ids = [s["id"] for s in t.spans()]
    assert len(set(ids)) == len(ids) == 6
    assert by_name["root"]["parent"] is None
    assert by_name["child.a"]["parent"] == by_name["root"]["id"]
    assert by_name["leaf"]["parent"] == by_name["child.a"]["id"]
    assert by_name["child.b"]["parent"] == by_name["root"]["id"]
    assert by_name["root.2"]["parent"] is None
    # the parent is the enclosing span of the SAME thread
    assert by_name["other.thread"]["parent"] is None
    path = t.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        args = {e["name"]: e["args"] for e in json.load(f)["traceEvents"]
                if e["ph"] == "X"}
    assert args["leaf"]["parent"] == by_name["child.a"]["id"]
    assert args["leaf"]["id"] == by_name["leaf"]["id"]
    assert args["root"]["parent"] is None


@pytest.mark.parametrize("where,module", [
    ("sntc_tpu.feature.chisq_selector", "feature"),
    ("sntc_tpu.models.tree.grower", "models"),
    (VectorAssembler, "feature"),
    (LogisticRegression, "models"),
    (Pipeline, "core"),
    ("toplevel", "toplevel"),
])
def test_module_of_is_the_layer_of_the_code(where, module):
    assert obs.module_of(where) == module


def _toy_frame(n=64):
    rng = np.random.default_rng(1)
    cols = {
        f"c{i}": np.abs(rng.normal(3, 2, n)).astype(np.float32)
        for i in range(3)
    }
    cols["label"] = (cols["c0"] > 3.0).astype(np.float64)
    return Frame(cols)


def test_pipeline_fit_emits_stage_spans_in_stage_order(mesh8):
    pipe = Pipeline(stages=[
        VectorAssembler(inputCols=["c0", "c1", "c2"], outputCol="features"),
        MinMaxScaler(inputCol="features", outputCol="scaled"),
        LogisticRegression(mesh=mesh8, featuresCol="scaled", maxIter=3),
    ])
    frame = _toy_frame()
    t = enable_tracing(capacity=256)
    model = pipe.fit(frame)
    spans = t.spans()
    (root,) = [s for s in spans if s["name"] == "pipeline.fit"]
    assert root["parent"] is None
    assert root["attrs"]["stages"] == 3 and root["attrs"]["module"] == "core"
    stage_spans = sorted(
        (s for s in spans if s["parent"] == root["id"]), key=lambda s: s["t0"]
    )
    assert [
        (s["name"], s["attrs"]["stage"], s["attrs"]["index"],
         s["attrs"]["module"])
        for s in stage_spans
    ] == [
        ("stage.transform", "VectorAssembler", 0, "feature"),
        ("stage.fit", "MinMaxScaler", 1, "feature"),
        ("stage.transform", "MinMaxScalerModel", 1, "feature"),
        # the last estimator's model is not run over the training frame
        ("stage.fit", "LogisticRegression", 2, "models"),
    ]
    # every other span of the fit hangs below one of the stage spans
    below = {s["id"] for s in stage_spans}
    for s in spans:
        if s["id"] in below or s is root:
            continue
        assert s["parent"] is not None
        below.add(s["id"])
    # a second fit is a second root with the next run number
    t.clear()
    pipe.fit(frame)
    (again,) = [s for s in t.spans() if s["name"] == "pipeline.fit"]
    assert again["attrs"]["run"] > root["attrs"]["run"]

    t.clear()
    model.transform(frame)
    spans = t.spans()
    (root,) = [s for s in spans if s["name"] == "pipeline.transform"]
    assert [
        (s["attrs"]["stage"], s["attrs"]["index"])
        for s in sorted(
            (s for s in spans if s["parent"] == root["id"]),
            key=lambda s: s["t0"],
        )
    ] == [("VectorAssembler", 0), ("MinMaxScalerModel", 1),
          ("LogisticRegressionModel", 2)]


def test_uploads_cross_the_one_routing_point(mesh8):
    from sntc_tpu.models.tree.grower import make_bagging_weights
    from sntc_tpu.parallel.collectives import shard_weights

    t = enable_tracing(capacity=64)
    before = _get("sntc_transfer_upload_bytes_total")
    w = make_bagging_weights(np.random.default_rng(0), True, 1.0, 4, 64, mesh8)
    assert w.shape == (4, 64)
    shard_weights(mesh8, np.ones(60, np.float32), 64)
    names = [(s["name"], s["attrs"].get("bytes")) for s in t.spans()]
    assert names == [
        ("rf.bagging", None), ("h2d.put", 4 * 64 * 4), ("h2d.put", 64 * 4),
    ]
    assert all(s["attrs"]["module"] in ("models", "parallel")
               for s in t.spans())
    # a placement says how many devices it cuts the array over and what
    # one of them takes: rows over the 8 devices of the mesh
    puts = [s["attrs"] for s in t.spans() if s["name"] == "h2d.put"]
    assert [(a["shards"], a["shard_bytes"]) for a in puts] == [
        (8, 4 * 64 * 4 // 8), (8, 64 * 4 // 8),
    ]
    # the bagging weights used to bypass the ledger (a bare device_put)
    assert (_get("sntc_transfer_upload_bytes_total") - before
            == 4 * 64 * 4 + 64 * 4)


def test_fit_scale_placement_is_one_put_span_and_one_device_pad(mesh8):
    """A fit-scale ``shard_batch`` call: one ``h2d.put`` an array over all
    its shard puts (``bytes`` the unpadded array's), then one ``h2d.pad``
    that says the pad ran on the device, then the weights' put."""
    from sntc_tpu.parallel import pad_rows, shard_batch

    n = 200_001
    X = np.ones((n, 8), np.float32)  # 6.4 MB: over the 1 MiB floor
    n_pad = pad_rows(n, 8)
    per_bytes = n_pad // 8 * 8 * 4
    t = enable_tracing(capacity=64)
    before = _get("sntc_transfer_upload_bytes_total")
    shard_batch(mesh8, X)
    spans = sorted(t.spans(), key=lambda s: s["t0"])
    assert [(s["name"], s["attrs"]["bytes"]) for s in spans] == [
        ("h2d.put", X.nbytes), ("h2d.pad", per_bytes), ("h2d.put", n_pad * 4),
    ]
    assert all(s["attrs"]["module"] == "parallel" for s in spans)
    assert all(s["parent"] is None for s in spans)
    assert [s["attrs"]["shards"] for s in spans] == [8, 8, 8]
    assert spans[0]["attrs"]["shard_bytes"] == -(-X.nbytes // 8)
    assert spans[1]["attrs"]["where"] == "device"
    assert "where" not in spans[0]["attrs"]
    # what crossed: the matrix, row 0 for the padded shard, the weights
    assert (_get("sntc_transfer_upload_bytes_total") - before
            == X.nbytes + 8 * 4 + n_pad * 4)
    # under the floor the host copy still runs, and says so
    t.clear()
    shard_batch(mesh8, np.ones((17, 8), np.float32))
    assert [(s["name"], s["attrs"].get("where")) for s in
            sorted(t.spans(), key=lambda s: s["t0"])] == [
        ("h2d.pad", "host"), ("h2d.put", None), ("h2d.put", None),
    ]


def test_one_vs_rest_boosting_opens_a_span_and_counts_each_round(mesh8):
    """``maxIter`` ``gbt.round`` spans (``module=models``, the round's
    number, K class trees), the grower's fetch nested in each, and
    ``maxIter`` / ``maxIter * K`` on the two counters."""
    from sntc_tpu.models import GBTClassifier, OneVsRest

    rng = np.random.default_rng(0)
    K, rounds = 3, 4
    y = rng.integers(0, K, size=400)
    X = (rng.normal(size=(400, 5)) + y[:, None]).astype(np.float32)
    frame = Frame({"features": X, "label": y.astype(np.float64)})
    t = enable_tracing(capacity=512)
    before = [_get(name, estimator="gbt_ovr") for name in
              ("sntc_boost_rounds_total", "sntc_boost_trees_total")]
    OneVsRest(classifier=GBTClassifier(
        mesh=mesh8, maxIter=rounds, maxDepth=2, seed=1
    )).fit(frame)
    spans = t.spans()
    opened = [s for s in spans if s["name"] == "gbt.round"]
    assert [(s["attrs"]["round"], s["attrs"]["trees"], s["attrs"]["module"])
            for s in opened] == [(m, K, "models") for m in range(rounds)]
    fetches = [s for s in spans if s["name"] == "d2h.fetch"]
    assert [s["parent"] for s in fetches] == [s["id"] for s in opened]
    for name in ("ovr.extract", "gbt.bin_edges"):
        (one,) = [s for s in spans if s["name"] == name]
        assert one["attrs"]["module"] == "models"
    assert len([s for s in spans if s["name"] == "gbt.mask"]) == rounds
    assert _get("sntc_boost_rounds_total", estimator="gbt_ovr") \
        - before[0] == rounds
    assert _get("sntc_boost_trees_total", estimator="gbt_ovr") \
        - before[1] == rounds * K


def _sum(name, **match):
    """One counter summed over the series that carry ``match``."""
    metric = registry().snapshot().get(name)
    return sum(
        row["value"] for row in (metric["series"] if metric else ())
        if all(row["labels"].get(k) == v for k, v in match.items())
    )


@pytest.fixture
def fresh_registry():
    """A registry of this test's own: a worker that has traced 64 programs
    already would fold the test's into the overflow series."""
    previous = obs.set_registry(MetricsRegistry())
    yield registry()
    obs.set_registry(previous)


def test_xla_compiles_counted_on_a_fresh_jit_not_on_a_repeat():
    import jax
    import jax.numpy as jnp

    from sntc_tpu.utils.compile_cache import enable_persistent_cache

    # no cache directory under tier-1 (SNTC_NO_COMPILE_CACHE): the listener
    # is installed before that early return, and installed once
    assert enable_persistent_cache() is None
    assert enable_persistent_cache() is None

    def body(x):
        return x * 2.0 + 1.0

    x = jnp.arange(8.0)
    x.block_until_ready()
    t = enable_tracing(capacity=64)
    n0 = _get("sntc_xla_compiles_total", outcome="compiled")
    s0 = _sum("sntc_xla_compile_seconds_total")
    fresh = jax.jit(body)
    fresh(x).block_until_ready()
    n1 = _get("sntc_xla_compiles_total", outcome="compiled")
    assert n1 == n0 + 1
    assert _sum("sntc_xla_compile_seconds_total") > s0
    (compiled,) = [s for s in t.spans() if s["name"] == "xla.compile"]
    assert compiled["attrs"]["outcome"] == "compiled"
    assert compiled["dur_s"] > 0
    assert compiled["attrs"]["module"] == "utils"
    assert "body" in compiled["attrs"]["program"]
    fresh(x).block_until_ready()  # the same jitted object: its own cache
    assert _get("sntc_xla_compiles_total", outcome="compiled") == n1
    jax.jit(body)(x).block_until_ready()  # jax knows the function itself
    assert _get("sntc_xla_compiles_total", outcome="compiled") == n1
    # a new closure over the old function is a new program to jax: what a
    # stage that builds its jit per fit pays on every fit
    jax.jit(lambda v: body(v))(x).block_until_ready()
    assert _get("sntc_xla_compiles_total", outcome="compiled") == n1 + 1
    assert _get("sntc_xla_compiles_total", outcome="cache_loaded") == 0


_PHASE_COUNTERS = (
    "sntc_xla_trace_seconds_total", "sntc_xla_lower_seconds_total",
    "sntc_xla_compile_seconds_total",
)


def _first_call(nested: bool):
    """A fresh program's first call, then a repeat: ``(phase seconds by
    program after the first call, the same after the repeat, wall seconds
    around the first call)``.  ``nested``: ``outer_fn`` calls the jitted
    ``inner_fn``, which jax traces inside the outer's trace."""
    import time

    import jax
    import jax.numpy as jnp

    from sntc_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()

    @jax.jit
    def inner_fn(x):
        for _ in range(40):  # a trace long enough to time
            x = jnp.sin(x) * 2.0 + 1.0
        return x

    def outer_fn(x):
        return (inner_fn(x) if nested else x) + 1.0

    x = jnp.arange(8.0)
    (x + 1.0).block_until_ready()  # the eager add's own program, up front
    jitted = jax.jit(outer_fn)

    def totals():
        return {
            name: {row["labels"].get("program"): row["value"]
                   for row in registry().snapshot().get(
                       name, {"series": ()})["series"]}
            for name in _PHASE_COUNTERS
        }

    def seconds():
        """By counter and program, what was added since ``before``."""
        return {name: {program: v - before[name].get(program, 0.0)
                       for program, v in now.items()
                       if v > before[name].get(program, 0.0)}
                for name, now in totals().items()}

    before = totals()
    t0 = time.time()
    jitted(x).block_until_ready()
    wall = time.time() - t0
    first = seconds()
    jitted(x).block_until_ready()
    return first, seconds(), wall


@pytest.mark.parametrize("case", [
    "three_phases_under_the_program", "a_repeat_adds_nothing",
    "compile_seconds_carry_the_outcome",
])
def test_first_call_of_a_fresh_jit_counts_its_phases(fresh_registry, case):
    first, repeat, wall = _first_call(nested=False)
    trace, lower, compiled = (first[name] for name in _PHASE_COUNTERS)
    if case == "three_phases_under_the_program":
        assert trace["outer_fn"] > 0
        assert lower["jit(outer_fn)"] > 0
        assert compiled["jit(outer_fn)"] > 0
        assert sum(sum(p.values()) for p in first.values()) <= wall
    elif case == "a_repeat_adds_nothing":
        assert repeat == first
    else:
        assert _sum("sntc_xla_compile_seconds_total", outcome="compiled",
                    program="jit(outer_fn)") == compiled["jit(outer_fn)"]
        assert set(compiled) == {"jit(outer_fn)"}
        assert _sum("sntc_xla_compile_seconds_total",
                    outcome="cache_loaded") == 0
        assert _sum("sntc_xla_compile_seconds_total") == \
            _sum("sntc_xla_compile_seconds_total", outcome="compiled")


@pytest.mark.parametrize("case", [
    "both_programs_have_a_series", "the_union_not_the_sum",
])
def test_nested_trace_is_counted_once(fresh_registry, case):
    first, _, wall = _first_call(nested=True)
    trace = first["sntc_xla_trace_seconds_total"]
    if case == "both_programs_have_a_series":
        assert trace["outer_fn"] > 0 and trace["inner_fn"] > 0
    else:
        assert sum(trace.values()) <= wall
        assert sum(sum(p.values()) for p in first.values()) <= wall


def test_own_seconds_charge_each_instant_to_the_innermost_span():
    """The listener's bookkeeping on hand-made spans, as jax reports them:
    at their close, the inner before the outer, phases mixed."""
    from sntc_tpu.utils.compile_cache import _CompileListener

    own = _CompileListener()._own_seconds
    assert own(1.0, 2.0) == 1.0            # an earlier, top-level span
    assert own(11.0, 12.0) == 1.0          # inner a
    assert own(12.5, 13.0) == 0.5          # a leaf inside inner b
    assert own(12.0, 14.0) == 1.5          # inner b: its leaf taken off
    assert own(10.0, 20.0) == 7.0          # outer: a and b off, whole
    assert own(21.0, 22.0) == 1.0          # the next top-level span
    assert own(0.0, 30.0) == 30.0 - 12.0   # and one around all of them


@pytest.mark.parametrize("sink", ["ring", "profiler"])
def test_first_call_spans_in_the_ring_and_the_marker_in_the_profiler(
    tmp_path, fresh_registry, sink,
):
    import jax
    import jax.numpy as jnp

    from sntc_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    x = jnp.arange(8.0)
    x.block_until_ready()

    def ring_body(v):
        return jnp.cos(v) * 3.0

    if sink == "ring":
        t = enable_tracing(capacity=256)
        with obs_span("stage.fit", stage="Fresh"):
            jax.jit(ring_body)(x).block_until_ready()
        spans = t.spans()
        (enclosing,) = [s for s in spans if s["name"] == "stage.fit"]
        lo, hi = enclosing["t0"], enclosing["t0"] + enclosing["dur_s"]
        mine = {s["name"]: s for s in spans
                if "ring_body" in s["attrs"].get("program", "")}
        assert set(mine) == {"xla.trace", "xla.lower", "xla.compile"}
        slack = 1e-3  # jax stamps time.time(), the ring perf_counter
        for s in mine.values():
            assert s["dur_s"] > 0
            assert lo - slack <= s["t0"]
            assert s["t0"] + s["dur_s"] <= hi + slack
            assert s["parent"] == enclosing["id"]
            assert s["attrs"]["module"] == "utils"
        assert mine["xla.compile"]["attrs"]["outcome"] == "compiled"
        assert "outcome" not in mine["xla.trace"]["attrs"]
        order = sorted(mine.values(), key=lambda s: s["t0"])
        assert [s["name"] for s in order] == \
            ["xla.trace", "xla.lower", "xla.compile"]
        # one after another: no phase of a program overlaps the next
        for a, b in zip(order, order[1:]):
            assert a["t0"] + a["dur_s"] <= b["t0"] + slack
        return
    with obs.device_trace(str(tmp_path)):
        jax.jit(lambda v: ring_body(v) + 1.0)(x).block_until_ready()
    events = _profiler_events(str(tmp_path))
    (marker,) = events["sntc:xla.compile"]
    # what benchmark/program_spans.py reads: a zero-length event with these
    assert set(marker[2]) == {"seconds", "outcome", "program", "module"}
    assert marker[2]["outcome"] == "compiled"
    assert marker[2]["module"] == "utils"
    assert marker[2]["seconds"] > 0
    assert "lambda" in marker[2]["program"]
    assert marker[1] - marker[0] < 1e6  # a marker: under a millisecond
    assert "sntc:xla.trace" not in events and "sntc:xla.lower" not in events


class _Identity(Transformer):
    def transform(self, frame):
        return frame


@pytest.mark.parametrize("case", ["set_by_the_first_fit", "not_by_a_transform",
                                  "unchanged_by_the_second"])
def test_first_fit_seconds_gauge(fresh_registry, monkeypatch, case):
    from sntc_tpu.core import base

    monkeypatch.setattr(base, "_first_fit_claimed", False)
    frame = Frame({"x": np.arange(4.0)})
    pipe = Pipeline(stages=[_Identity()])
    name = "sntc_pipeline_first_fit_seconds"
    if case == "not_by_a_transform":
        # _RUNS is shared with transform: run=1 here is no fit
        base.PipelineModel(stages=[_Identity()]).transform(frame)
        assert registry().get(name) is None
        return
    pipe.fit(frame)
    first = registry().get(name)
    assert first is not None and first > 0
    if case == "unchanged_by_the_second":
        pipe.fit(frame)
        Pipeline(stages=[_Identity(), _Identity()]).fit(frame)
        assert registry().get(name) == first


@pytest.mark.parametrize("case", ["positive", "set_once"])
def test_device_ready_seconds_gauge(fresh_registry, monkeypatch, case):
    from sntc_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(mesh_mod, "_device_ready_noted", False)
    name = "sntc_process_device_ready_seconds"
    mesh_mod.default_mesh()
    ready = registry().get(name)
    # this process is older than its import of jax and younger than a day
    assert ready is not None and 0 < ready < 86_400
    if case == "set_once":
        mesh_mod.default_mesh(1)
        mesh_mod.make_mesh()
        assert registry().get(name) == ready


def test_the_manifests_set_up_readers_load_and_read_this_registry(
    fresh_registry, monkeypatch,
):
    """Every per-layer metric of ``BENCHMARK.json`` that moves ``setup_s``
    has a reader under ``benchmark/layer_metrics/`` that loads, gives no
    number on an empty registry and reads what the program counts; the
    manifest passes the benchmark's own check."""
    bench = os.path.join(REPO, "benchmark")
    monkeypatch.syspath_prepend(bench)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]
             if m["moves"] == "setup_s"]
    assert len(names) == 8

    def load(kind, name):
        spec = importlib.util.spec_from_file_location(
            "bench_" + name.replace(".", "_"),
            os.path.join(bench, kind, name + ".py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    assert load("", "check_manifest").check(manifest) == []
    readers = {name: load("layer_metrics", name).read for name in names}
    assert {name: read({}) for name, read in readers.items()} == \
        dict.fromkeys(names)
    obs.set_gauge("sntc_process_device_ready_seconds", 11.0)
    obs.set_gauge("sntc_pipeline_first_fit_seconds", 19.5)
    obs.inc("sntc_xla_trace_seconds_total", 1.5, program="f")
    obs.inc("sntc_xla_trace_seconds_total", 0.5, program="g")
    obs.inc("sntc_xla_lower_seconds_total", 3.0, program="jit(f)")
    obs.inc("sntc_xla_compile_seconds_total", 0.25, outcome="cache_loaded",
            program="jit(f)")
    obs.inc("sntc_xla_compiles_total", outcome="cache_loaded")
    assert {name: read({}) for name, read in readers.items()} == {
        "device_ready_s": 11.0, "first_fit_s": 19.5,
        "first_call_s.trace": 2.0, "first_call_s.lower": 3.0,
        "first_call_s.compile": 0.0, "first_call_s.load": 0.25,
        "first_call_programs": 1.0, "first_call_compiled": 0.0,
    }


# ---------------------------------------------------------------------------
# the event→metrics bridge + event timestamps
# ---------------------------------------------------------------------------


def test_events_carry_wall_and_monotonic_timestamps():
    rec = R.emit_event(event="retry", site="stream.read", attempt=1)
    assert rec["ts"] > 0 and rec["mono"] > 0
    (tail,) = R.recent_events(event="retry")[-1:]
    assert tail["ts"] == rec["ts"] and tail["mono"] == rec["mono"]


def test_bridge_counts_events_and_splits_tenant_sites():
    assert split_tenant_site(
        {"site": "tenant/a/sink.write"}
    ) == ("sink.write", "a")
    assert split_tenant_site(
        {"site": "stream.read", "tenant": "b"}
    ) == ("stream.read", "b")
    before = _get(
        "sntc_events_total", event="retry", site="sink.write", tenant="z"
    )
    R.emit_event(event="retry", site="tenant/z/sink.write", attempt=1)
    assert (
        _get("sntc_events_total", event="retry", site="sink.write",
             tenant="z")
        == before + 1
    )


def test_bridge_rows_rejected_reasons_and_shed_offsets():
    before_nf = _get(
        "sntc_rows_rejected_total", reason="non_finite", tenant="q"
    )
    before_shed = _get("sntc_shed_offsets_total", tenant="q")
    R.emit_event(
        event="rows_rejected", site="tenant/q/source.parse", count=3,
        reasons={"non_finite": 2, "ragged_row": 1}, tenant="q",
    )
    R.emit_event(
        event="load_shed", site="tenant/q/stream.read", tenant="q",
        policy="oldest", offsets_shed=7, start=0, end=7,
    )
    assert (
        _get("sntc_rows_rejected_total", reason="non_finite", tenant="q")
        == before_nf + 2
    )
    assert (
        _get("sntc_shed_offsets_total", tenant="q") == before_shed + 7
    )


def test_health_report_mirrors_gauge_and_snapshot_has_both_clocks():
    h = R.HealthMonitor(clock=lambda: 42.0)
    h.report("mycomp", R.HealthState.DEGRADED, "testing")
    assert _get("sntc_health_state", component="mycomp") == 1
    entry = h.snapshot()["components"]["mycomp"]
    assert entry["since"] == 42.0
    assert entry["since_wall"] > 0
    h.report("mycomp", R.HealthState.OK)
    assert _get("sntc_health_state", component="mycomp") == 0


def test_breaker_transitions_set_state_gauge():
    br = R.CircuitBreaker(
        "obs.test.site", window=4, min_calls=2, failure_threshold=0.5,
        cooldown_s=0.0, clock=lambda: 0.0,
    )
    br.record_failure()
    br.record_failure()
    assert br.state == "half_open"  # cooldown 0: open → half_open
    # the OPEN transition wrote 2, the half_open probe window wrote 1
    assert _get("sntc_breaker_state", site="obs.test.site") == 1


# ---------------------------------------------------------------------------
# per-engine transfer-ledger attribution (satellite: the bare
# process-global conflated tenants)
# ---------------------------------------------------------------------------


def test_ledger_scope_attribution_and_metrics_mirror():
    glob = transfer_ledger()
    g0 = glob.snapshot()
    a = TransferLedger(tenant="ledger-a")
    b = TransferLedger(tenant="ledger-b")
    assert active_ledgers() == (glob,)
    with ledger_scope(a):
        assert active_ledgers() == (glob, a)
        for led in active_ledgers():
            led.record_uploads(2, 100)
    with ledger_scope(b):
        for led in active_ledgers():
            led.record_uploads(1, 50)
            led.record_downloads(1, 10)
    assert active_ledgers() == (glob,)
    # per-engine ledgers saw only their own scope's transfers
    assert (a.uploads, a.downloads) == (2, 0)
    assert (b.uploads, b.downloads) == (1, 1)
    # the global saw everything (the default process-wide view)
    g1 = glob.snapshot()
    assert g1["uploads"] - g0["uploads"] == 3
    assert g1["download_bytes"] - g0["download_bytes"] == 10
    # tenant-labeled metric series mirror the per-engine ledgers exactly
    assert _get("sntc_transfer_uploads_total", tenant="ledger-a") == 2
    assert _get("sntc_transfer_uploads_total", tenant="ledger-b") == 1
    assert (
        _get("sntc_transfer_download_bytes_total", tenant="ledger-b")
        == 10
    )
    # anonymous engine ledgers do NOT mirror (the unlabeled series must
    # stay exactly the global ledger)
    anon = TransferLedger()
    unlabeled0 = _get("sntc_transfer_uploads_total")
    anon.record_uploads(5, 5)
    assert _get("sntc_transfer_uploads_total") == unlabeled0


def test_nested_scopes_record_to_both():
    a = TransferLedger()
    b = TransferLedger()
    with ledger_scope(a), ledger_scope(b):
        for led in active_ledgers()[1:]:
            led.record_downloads(1)
    assert a.downloads == 1 and b.downloads == 1


# ---------------------------------------------------------------------------
# end-to-end: one serve run's Prometheus snapshot agrees with the
# legacy ledger views (compile / transfer / shed / tenant series)
# ---------------------------------------------------------------------------


class _Identity(Transformer):
    def transform(self, frame):
        return frame


def _fused_served_model(mesh):
    """A tiny fitted pipeline with a real fused segment (assembler runs
    eagerly per the single-upload rule; scaler+LR fuse)."""
    rng = np.random.default_rng(0)
    cols = {
        f"c{i}": np.abs(rng.normal(3, 2, 240)).astype(np.float32)
        for i in range(4)
    }
    cols["label"] = (cols["c0"] > 3.0).astype(np.float64)
    f = Frame(cols)
    pm = Pipeline(stages=[
        VectorAssembler(inputCols=[f"c{i}" for i in range(4)],
                        outputCol="features"),
        MinMaxScaler(inputCol="features", outputCol="scaled"),
        LogisticRegression(mesh=mesh, featuresCol="scaled", maxIter=15),
    ]).fit(f)
    from sntc_tpu.fuse import compile_pipeline, fused_segments

    fused = compile_pipeline(pm)
    assert fused_segments(fused)
    serve_frames = [
        Frame({
            f"c{i}": np.abs(rng.normal(3, 2, 16)).astype(np.float32)
            for i in range(4)
        })
        for _ in range(4)
    ]
    return fused, serve_frames


def test_e2e_prometheus_snapshot_agrees_with_legacy_ledgers(
    mesh8, tmp_path
):
    fused, frames_a = _fused_served_model(mesh8)
    _, frames_b = _fused_served_model(mesh8)
    from sntc_tpu.serve.transform import BatchPredictor

    pred = BatchPredictor(fused, bucket_rows=8)
    spec_a = TenantSpec(
        tenant_id="obs-a", model=pred,
        source=MemorySource(frames_a), sink=MemorySink(),
    )
    # tenant b sheds: backlog of 6 one-offset batches over a cap of 2
    spec_b = TenantSpec(
        tenant_id="obs-b", model=pred,
        source=MemorySource(frames_b + frames_b[:2]),
        sink=MemorySink(),
        max_pending_batches=2, shed_policy="oldest",
    )
    before = {
        "compile": _get("sntc_predict_compile_events_total"),
        "fuse_compile": _get("sntc_fuse_compile_events_total"),
        "up_global": _get("sntc_transfer_uploads_total"),
        "down_global": _get("sntc_transfer_downloads_total"),
        "shed_b": _get("sntc_shed_offsets_total", tenant="obs-b"),
        "rows_a": _get("sntc_rows_committed_total", tenant="obs-a"),
        "rows_b": _get("sntc_rows_committed_total", tenant="obs-b"),
        "ticks": _get("sntc_daemon_ticks_total"),
    }
    glob0 = transfer_ledger().snapshot()
    compile0 = pred.compile_events
    daemon = ServeDaemon(
        [spec_a, spec_b], str(tmp_path / "root"), shape_buckets=8
    )
    try:
        daemon.process_available()
        status = daemon.status()
        ta = daemon._by_id["obs-a"]
        tb = daemon._by_id["obs-b"]
        # tenant rows: registry series == the daemon's own accounting
        assert (
            _get("sntc_rows_committed_total", tenant="obs-a")
            - before["rows_a"]
            == ta.rows_done
        )
        assert (
            _get("sntc_rows_committed_total", tenant="obs-b")
            - before["rows_b"]
            == tb.rows_done
        )
        assert (
            _get("sntc_batches_committed_total", tenant="obs-a")
            == ta.batches_done
        )
        # shed: registry series == the tenant's journaled shed ledger
        assert tb.shed_total_offsets > 0
        assert (
            _get("sntc_shed_offsets_total", tenant="obs-b")
            - before["shed_b"]
            == tb.shed_total_offsets
        )
        # compile ledger: registry delta == the shared predictor's delta
        assert (
            _get("sntc_predict_compile_events_total") - before["compile"]
            == pred.compile_events - compile0
        )
        assert status["recompiles_after_warmup"] is None  # not marked
        # transfers: the unlabeled series delta == the global ledger
        # delta, and the per-tenant series sum to it (every dispatch in
        # this window came from the two scoped engines)
        glob1 = transfer_ledger().snapshot()
        up_delta = _get("sntc_transfer_uploads_total") - before[
            "up_global"
        ]
        assert up_delta == glob1["uploads"] - glob0["uploads"]
        assert up_delta > 0
        assert (
            _get("sntc_transfer_uploads_total", tenant="obs-a")
            + _get("sntc_transfer_uploads_total", tenant="obs-b")
            >= up_delta
        )
        # per-engine ledgers ride pipeline_stats as the legacy-style view
        ledger_a = ta.query.pipeline_stats()["transfers"]
        assert ledger_a["uploads"] == _get(
            "sntc_transfer_uploads_total", tenant="obs-a"
        )
        assert _get("sntc_daemon_ticks_total") > before["ticks"]
        # the exposition carries all of it
        prom = registry().to_prometheus()
        assert 'sntc_rows_committed_total{tenant="obs-a"}' in prom
        assert 'sntc_shed_offsets_total{tenant="obs-b"}' in prom
        assert "sntc_predict_compile_events_total" in prom
        assert 'sntc_tenant_state{tenant="obs-a"} 0' in prom
    finally:
        daemon.close()


def test_engine_transfer_ledger_not_conflated_across_tenants(
    mesh8, tmp_path
):
    """THE satellite regression: two tenant streams on one shared fused
    predictor used to conflate upload/download counts in the one
    process-global ledger; per-engine ledgers attribute them."""
    fused, frames = _fused_served_model(mesh8)
    from sntc_tpu.serve.transform import BatchPredictor

    pred = BatchPredictor(fused)
    specs = [
        TenantSpec(tenant_id=tid, model=pred,
                   source=MemorySource(list(frames[:n])),
                   sink=MemorySink())
        for tid, n in (("conf-a", 3), ("conf-b", 1))
    ]
    daemon = ServeDaemon(specs, str(tmp_path / "root"))
    try:
        daemon.process_available()
        la = daemon._by_id["conf-a"].query.transfer
        lb = daemon._by_id["conf-b"].query.transfer
        assert la.dispatches == 3 and lb.dispatches == 1
        assert la.uploads > lb.uploads  # 3 batches vs 1, attributed
        assert la.tenant == "conf-a" and lb.tenant == "conf-b"
    finally:
        daemon.close()


# ---------------------------------------------------------------------------
# single-tenant engine: per-batch metrics without labels
# ---------------------------------------------------------------------------


def test_single_tenant_engine_emits_unlabeled_series(tmp_path):
    from sntc_tpu.serve import StreamingQuery

    frames = [Frame({"x": np.arange(6.0)}) for _ in range(2)]
    b0 = _get("sntc_batches_committed_total")
    r0 = _get("sntc_rows_committed_total")
    q = StreamingQuery(
        _Identity(), MemorySource(frames), MemorySink(),
        str(tmp_path / "ckpt"), max_batch_offsets=1,
    )
    assert q.process_available() == 2
    assert _get("sntc_batches_committed_total") - b0 == 2
    assert _get("sntc_rows_committed_total") - r0 == 12
    assert q.pipeline_stats()["transfers"]["dispatches"] == 0  # unfused


# ---------------------------------------------------------------------------
# metric-name drift check (tier-1 wiring of check_metric_names)
# ---------------------------------------------------------------------------


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_metric_names_consistent_code_catalog_docs():
    checker = _load_script("check_metric_names")
    assert checker.check() == []
