"""The whole-set boosted cell (``cicflow_gbt_whole.fit``: rows sharded over
four chips, histograms summed by ``psum``) under the harness's own decision,
at test size on four virtual CPU devices, the ``tree_hist`` kernel per shard
through the Pallas interpreter: the rehearsal is correct, the bfloat16
control and a planted fault are refused, and the two readers of the
collective give the numbers worked out by hand from a hand-made trace.

    python3 -m pytest benchmark/tests -q      (CPU; not part of tier-1)
"""

import json
import os
import types

# four virtual devices, asked for before JAX makes its CPU backend
if "--xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()

import pytest

import faults_gbt
import gen
import run

CELL = "cicflow_gbt_whole.fit"
S = 1e9  # one second, in the trace's nanoseconds


@pytest.fixture(autouse=True)
def four_devices(monkeypatch):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("JAX made its CPU backend before this file asked for "
                    "four devices")
    # as on the chips: the kernel per shard, then the psum
    monkeypatch.setenv("SNTC_TREE_HIST", "pallas")


def _last_line(capsys, seed=5):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.2", "--trace", "0", "--rehearse-cpu"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_rehearsal_of_the_cell_is_correct(capsys):
    from sntc_tpu.obs import registry

    res = _last_line(capsys)
    assert res["correct"] is True, res["checks"]
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] >= 4
    assert set(res["metrics"]) == {"setup_s", "fit_s"}
    # the warm-up fit and the window's one: 100 all-reduces each, and the
    # mesh the fit took
    assert registry().get("sntc_kernel_tree_hist_psum_total") % 100 == 0
    assert registry().get("sntc_collective_mesh_devices", axis="data") == 4


def test_bfloat16_control_is_refused():
    cell, cfg, traffic = run.resolve_cell(run.load_json(
        run.ROOT, "BENCHMARK.json"), CELL)
    assert cell["chips"] == 4 and cfg["rows"] == cfg["rows_total"]
    adapter = run.load_module("estimators", cfg["estimator"])
    columns = gen.generate_columns(int(cfg["rehearse_rows"]), 77)
    s = run.model_seed(77)
    numbers = adapter.compare(
        "fit", adapter.control_product("fit", cfg, columns, s, "bf16"),
        cfg, columns, s,
    )
    correct, checks = run.judge(numbers, cfg["limits"]["fit"])
    assert not correct, checks
    assert [k for k, c in checks.items() if c["value"] > c["limit"]], checks


def test_planted_fault_is_refused(capsys):
    with faults_gbt.FAULTS["altered_step"](run, "fit", "gbt"):
        res = _last_line(capsys)
    assert res["correct"] is False, res["checks"]


# ---- the readers of the collective, on a hand-made trace -------------------


def _event(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=end - start)


def _plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n.replace("_", " "), events=evs)
        for n, evs in lines.items()
    ])


AR = ("%psum.35 = f32[15,78,32,3]{2,1,0,3:T(8,128)S(1)} all-reduce("
      "%slice_bitcast_fusion.1), channel_id=1, replica_groups={{0,1,2,3}}")
KERNEL = "%level_histogram_pallas.1 = f32[2496,144]{1,0} custom-call(...)"


def _fake_profile():
    """Two chips, a window of 2 .. 12 s, two fits: chip 0 runs three
    all-reduces inside the window (0.1 s each) and one before it; chip 1
    runs the same three and waits longer in each (0.3 s)."""
    def ops(ar_s):
        evs = [_event(AR, 1.0 * S, (1.0 + ar_s) * S)]  # the warm-up's
        for t in (3.0, 5.0, 7.0):
            evs.append(_event(KERNEL, t * S, (t + 1.0) * S))
            evs.append(_event(AR, (t + 1.0) * S, (t + 1.0 + ar_s) * S))
        return evs

    host = _plane("/host:CPU", python=[_event("bench:window", 2 * S, 12 * S)])
    return types.SimpleNamespace(planes=[
        host,
        _plane("/device:TPU:0", XLA_Ops=ops(0.1), XLA_Modules=[]),
        _plane("/device:TPU:1", XLA_Ops=ops(0.3), XLA_Modules=[]),
    ])


def test_readers_of_the_collective_on_a_hand_made_trace(monkeypatch):
    import jax.profiler

    import reduce_trace

    fake = types.SimpleNamespace(from_file=lambda path: _fake_profile())
    monkeypatch.setattr(jax.profiler, "ProfileData", fake)
    trace = reduce_trace.reduce_file("hand-made", 2)
    # a chip is busy 3 x (1 + its all-reduce): 3.3 and 3.9 s, mean 3.6
    assert trace["busy_s"] == pytest.approx(3.6)
    ctx = {"trace": trace, "passes": [None, None]}
    share = run.load_module("layer_metrics", "collective_share.fit").read(ctx)
    # all-reduce seconds, mean over the chips: (0.3 + 0.9) / 2 = 0.6 of 3.6
    assert share == pytest.approx(100.0 * 0.6 / 3.6)
    count = run.load_module("layer_metrics", "hist_psums.fit").read(ctx)
    assert count == pytest.approx(3 / 2)  # chip 0's, in the window, per fit


def test_readers_give_nothing_without_an_all_reduce():
    """One chip, or a CPU rehearsal: no number, never 0."""
    trace = {"busy_s": 7.8, "window_s": 12.3, "n_device_planes": 1,
             "device_seconds_by_name": {KERNEL: 5.9}, "path": None}
    ctx = {"trace": trace, "passes": [None]}
    assert run.load_module(
        "layer_metrics", "collective_share.fit").read(ctx) is None
    ctx = {"trace": None, "passes": [None]}
    for name in ("collective_share.fit", "hist_psums.fit"):
        assert run.load_module("layer_metrics", name).read(ctx) is None
