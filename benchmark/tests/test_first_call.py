"""The eight readers that split ``setup_s`` (``first_call.py`` and its files
under ``layer_metrics/``) against registries with planted values.

    python3 -m pytest benchmark/tests -q      (CPU; not part of tier-1)
"""

import pytest

import run
from sntc_tpu import obs
from sntc_tpu.obs.metrics import MetricsRegistry

READERS = (
    "device_ready_s", "first_fit_s", "first_call_s.trace",
    "first_call_s.lower", "first_call_s.compile", "first_call_s.load",
    "first_call_programs", "first_call_compiled",
)


@pytest.fixture
def planted():
    """A registry of the test's own in the program's place; two label sets
    a metric, so the third program of a phase folds into the overflow."""
    reg = MetricsRegistry(max_label_sets=2)
    previous = obs.set_registry(reg)
    yield reg
    obs.set_registry(previous)


def read(name):
    return run.load_module("layer_metrics", name).read({})


def plant_cold(reg):
    reg.set_gauge("sntc_process_device_ready_seconds", 12.5)
    reg.set_gauge("sntc_pipeline_first_fit_seconds", 38.25)
    for program, s in (("_grow_fused", 2.0), ("agg", 0.5), ("_where", 0.25)):
        reg.inc("sntc_xla_trace_seconds_total", s, program=program)
        reg.inc("sntc_xla_lower_seconds_total", 2 * s,
                program=f"jit({program})")
        reg.inc("sntc_xla_compile_seconds_total", 4 * s, outcome="compiled",
                program=f"jit({program})")
        reg.inc("sntc_xla_compiles_total", outcome="compiled")


@pytest.mark.parametrize("name", READERS)
def test_an_empty_registry_gives_no_number(planted, name):
    assert read(name) is None


@pytest.mark.parametrize("name, want", [
    ("device_ready_s", 12.5), ("first_fit_s", 38.25),
    ("first_call_s.trace", 2.75), ("first_call_s.lower", 5.5),
    ("first_call_s.compile", 11.0), ("first_call_s.load", 0.0),
    ("first_call_programs", 3.0), ("first_call_compiled", 3.0),
])
def test_a_cold_process_sums_over_programs_overflow_included(
    planted, capsys, name, want,
):
    plant_cold(planted)
    # the third program went to the overflow series, and still counts
    assert planted.get("sntc_xla_trace_seconds_total", overflow="true") == 0.25
    assert planted.get("sntc_xla_compile_seconds_total",
                       overflow="true") == 1.0
    assert read(name) == want
    err = capsys.readouterr().err
    if name.startswith("first_call_s.") and want:
        # the programs with the most seconds, largest first
        assert "_grow_fused" in err and "(overflow)" in err
        assert err.index("_grow_fused") < err.index("agg")


@pytest.mark.parametrize("name, want", [
    ("first_call_s.compile", 0.0), ("first_call_s.load", 1.5),
    ("first_call_programs", 2.0), ("first_call_compiled", 0.0),
])
def test_a_warm_process_loads_everything(planted, name, want):
    for program, s in (("jit(a)", 1.0), ("jit(b)", 0.5)):
        planted.inc("sntc_xla_compile_seconds_total", s,
                    outcome="cache_loaded", program=program)
        planted.inc("sntc_xla_compiles_total", outcome="cache_loaded")
    assert read(name) == want


@pytest.mark.parametrize("name, want", [
    ("first_call_s.compile", 4.0 + 0.125), ("first_call_s.load", 1.0),
    ("first_call_programs", 3.0), ("first_call_compiled", 2.0),
])
def test_the_two_outcomes_are_kept_apart(planted, name, want):
    planted.inc("sntc_xla_compile_seconds_total", 4.0, outcome="compiled",
                program="jit(a)")
    planted.inc("sntc_xla_compile_seconds_total", 1.0, outcome="cache_loaded",
                program="jit(b)")
    # a third label set: folded, its outcome lost; in a process that met
    # both outcomes the overflow's seconds go to ``compiled`` and are
    # counted once
    planted.inc("sntc_xla_compile_seconds_total", 0.125, outcome="compiled",
                program="jit(c)")
    planted.inc("sntc_xla_compiles_total", 2, outcome="compiled")
    planted.inc("sntc_xla_compiles_total", outcome="cache_loaded")
    assert read(name) == want


def test_a_program_from_before_the_outcome_label_gives_no_seconds(planted):
    """The parent's counter has no labels: nothing to split, so no number
    (and its count of executables still reads)."""
    from sntc_tpu.obs import metrics

    spec = dict(metrics.CATALOG["sntc_xla_compile_seconds_total"], labels=())
    old = metrics.CATALOG["sntc_xla_compile_seconds_total"]
    metrics.CATALOG["sntc_xla_compile_seconds_total"] = spec
    try:
        planted.inc("sntc_xla_compile_seconds_total", 7.0)
        planted.inc("sntc_xla_compiles_total", outcome="cache_loaded")
        assert read("first_call_s.compile") is None
        assert read("first_call_s.load") is None
        assert read("first_call_s.trace") is None
        assert read("first_call_programs") == 1.0
    finally:
        metrics.CATALOG["sntc_xla_compile_seconds_total"] = old
