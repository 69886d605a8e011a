"""``program_spans``: the partition of the device's idle time over the
program's own spans, on hand-made spans and idle intervals (nanoseconds, as
the trace gives them).

    python3 -m pytest benchmark/tests -q      (CPU; not part of tier-1)
"""

import pytest

import program_spans as ps

S = 1e9  # one second, in the trace's nanoseconds

FEATURE, MODELS, PARALLEL, CORE = (
    {"module": m} for m in ("feature", "models", "parallel", "core")
)

#: one fit on one thread, 0 .. 100 s
SPANS = [
    ("pipeline.fit", 1 * S, 99 * S, dict(CORE, stages=2)),
    ("stage.fit", 2 * S, 40 * S, dict(FEATURE, stage="ChiSqSelector")),
    ("chi2.bin_edges", 3 * S, 5 * S, FEATURE),
    ("h2d.put", 6 * S, 8 * S, dict(PARALLEL, bytes=10)),
    ("stage.fit", 41 * S, 98 * S, dict(MODELS, stage="RandomForestClassifier")),
    ("rf.bagging", 50 * S, 60 * S, MODELS),
    ("xla.compile", 55 * S, 55 * S, {"module": "utils", "outcome": "compiled"}),
]

#: the device's idle intervals
IDLE = [
    (0 * S, 1.5 * S),    # 1 s before any span, 0.5 s under pipeline.fit alone
    (2.5 * S, 7 * S),    # 0.5 stage.fit[Chi..], 2 bin_edges, 1 stage.fit, 1 h2d.put
    (40 * S, 42 * S),    # 1 s between the stages (pipeline.fit), 1 s RF stage
    (52 * S, 53 * S),    # 1 s rf.bagging
    (98.5 * S, 100 * S),  # 0.5 pipeline.fit, 1 s after every span
]


def test_four_groups_partition_the_idle_total():
    got = ps.attribute(IDLE, SPANS)
    assert got["total_s"] == pytest.approx(10.5)
    assert set(got["by_group"]) == set(ps.GROUPS)
    assert sum(got["by_group"].values()) == pytest.approx(got["total_s"])
    assert got["by_group"] == pytest.approx({
        "feature": 3.5, "models": 2.0, "upload": 1.0, "unattributed": 4.0,
    })
    assert sum(r["idle_s"] for r in got["by_span"].values()) == \
        pytest.approx(got["total_s"])


def test_nesting_picks_the_innermost_span():
    rows = ps.attribute(IDLE, SPANS)["by_span"]
    assert rows["chi2.bin_edges"]["idle_s"] == pytest.approx(2.0)
    assert rows["h2d.put"]["idle_s"] == pytest.approx(1.0)
    assert rows["stage.fit[ChiSqSelector]"]["idle_s"] == pytest.approx(1.5)
    assert rows["stage.fit[RandomForestClassifier]"]["idle_s"] == \
        pytest.approx(1.0)
    assert rows["rf.bagging"]["idle_s"] == pytest.approx(1.0)
    assert rows["h2d.put"]["group"] == "upload"  # by name, whatever module
    assert rows["rf.bagging"]["leaf"] and rows["chi2.bin_edges"]["leaf"]
    assert not rows["stage.fit[ChiSqSelector]"]["leaf"]
    assert not rows["pipeline.fit"]["leaf"]
    assert rows["rf.bagging"]["span_s"] == pytest.approx(10.0)


@pytest.mark.parametrize("name,attrs,group", [
    ("h2d.put", PARALLEL, "upload"),
    ("h2d.pad", PARALLEL, "upload"),
    ("stage.fit", FEATURE, "feature"),
    ("d2h.fetch", MODELS, "models"),
    ("pipeline.fit", CORE, "unattributed"),
    ("mesh.resize", PARALLEL, "unattributed"),
    ("no.module", {}, "unattributed"),
])
def test_group_of_a_span(name, attrs, group):
    assert ps.group_of(name, attrs) == group


def test_gap_under_no_span_is_unattributed():
    got = ps.attribute(IDLE, SPANS)
    assert got["by_span"][ps.NO_SPAN]["idle_s"] == pytest.approx(2.0)
    assert got["by_span"]["pipeline.fit"]["idle_s"] == pytest.approx(2.0)
    only = ps.attribute([(200 * S, 203 * S)], SPANS)
    assert only["by_group"] == pytest.approx({
        "feature": 0.0, "models": 0.0, "upload": 0.0, "unattributed": 3.0,
    })


def test_seconds_are_per_pass():
    one, two = ps.attribute(IDLE, SPANS), ps.attribute(IDLE, SPANS, per=2)
    assert two["total_s"] == pytest.approx(one["total_s"] / 2)
    assert two["by_group"]["feature"] == pytest.approx(1.75)


def _ctx(tmp_path, spans):
    """A context over a real ``.xplane.pb`` made on the CPU: the spans as
    the program would write them, no device plane."""
    import jax

    import reduce_trace

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench:window"):
        for name in spans:
            with jax.profiler.TraceAnnotation(name, module="models"):
                pass
    jax.profiler.stop_trace()
    return {"trace": {"path": reduce_trace.find_xplane(str(tmp_path))},
            "passes": [1.0]}


def test_empty_trace_gives_none(tmp_path):
    ctx = _ctx(tmp_path, [])
    for group in ps.GROUPS:
        assert ps.idle_seconds(ctx, group) is None
    assert ps.compiles_in_window(ctx) is None
    assert ps.idle_seconds({"passes": [1.0]}, "models") is None  # untraced


def test_no_device_plane_gives_none_but_counts_compiles(tmp_path):
    ctx = _ctx(tmp_path, ["sntc:rf.bagging", "sntc:xla.compile",
                          "sntc:xla.compile"])
    assert ps.idle_seconds(ctx, "models") is None
    assert ps.compiles_in_window(ctx) == 2
    assert "program_spans" in ctx  # read once, kept for the next reader
