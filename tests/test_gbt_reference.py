"""The one-vs-rest boosted fit against the benchmark's plain reference
(``benchmark/reference_gbt.py``, which imports nothing from ``sntc_tpu``), at
a small size on the CPU: on seeded frames every number the benchmark's
``compare`` reads lies under the configuration's limit, the bfloat16 control
(the reference in the program's place with its statistics rounded to one
bfloat16 term before they are summed) lies over at least one, and the
harness's CPU rehearsal of the cell comes out ``correct``.  The same seeds
again as the four-chip deployment runs them (``cicflow_gbt_whole``): rows
sharded over ``default_mesh(4)``, the ``tree_hist`` kernel per shard (the
Pallas interpreter here) and its histograms summed by ``psum``."""

import json
import os
import sys

import pytest

_BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"
)
SEEDS = (3, 2147483700, 77)


@pytest.fixture(scope="module")
def bench():
    """The harness's modules, importable the way ``run.py`` imports them."""
    for p in (_BENCH, os.path.dirname(_BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import gen
    import run

    cell, cfg, traffic = run.resolve_pair("cicflow_gbt", "fit_full")
    whole = run.resolve_pair("cicflow_gbt_whole", "fit_full")[1]
    adapter = run.load_module("estimators", cfg["estimator"])
    return {"run": run, "gen": gen, "cfg": cfg, "adapter": adapter,
            "limits": cfg["limits"]["fit"], "rows": cfg["rehearse_rows"],
            # by the chips the configuration's deployment takes
            "cfgs": {1: cfg, 4: whole}}


@pytest.fixture(scope="module")
def frames(bench):
    return {s: bench["gen"].generate_columns(bench["rows"], s) for s in SEEDS}


@pytest.mark.parametrize("chips", (1, 4))
@pytest.mark.parametrize("seed", SEEDS)
def test_program_reads_under_every_limit(bench, frames, seed, chips,
                                         monkeypatch):
    from sntc_tpu.obs import registry
    from sntc_tpu.parallel.mesh import default_mesh

    run, adapter, cfg = bench["run"], bench["adapter"], bench["cfgs"][chips]
    limits = cfg["limits"]["fit"]
    if chips > 1:  # as on the chips: the kernel per shard, then the psum
        monkeypatch.setenv("SNTC_TREE_HIST", "pallas")
    def counted():
        return registry().get("sntc_kernel_tree_hist_psum_total") or 0

    psums = counted()
    s = run.model_seed(seed)
    res = run.KINDS["fit"](adapter, cfg, frames[seed], default_mesh(chips),
                           s)()
    product = adapter.extract_product("fit", res)
    assert product["feature"].shape == (15, 20, 63)
    numbers = adapter.compare("fit", product, cfg, frames[seed], s)
    correct, checks = run.judge(numbers, limits)
    assert correct, checks
    assert set(limits) <= set(numbers)
    # one all-reduce a level a round, counted a fit and not a compilation
    assert counted() - psums == (cfg["maxIter"] * cfg["maxDepth"] if chips > 1 else 0)


@pytest.mark.parametrize("seed", SEEDS)
def test_bfloat16_control_reads_over_a_limit(bench, frames, seed):
    run, adapter, cfg = bench["run"], bench["adapter"], bench["cfg"]
    s = run.model_seed(seed)
    numbers = adapter.compare(
        "fit", adapter.control_product("fit", cfg, frames[seed], s, "bf16"),
        cfg, frames[seed], s,
    )
    correct, checks = run.judge(numbers, bench["limits"])
    assert not correct, checks
    over = [k for k, c in checks.items() if c["value"] > c["limit"]]
    assert over, checks  # refused by a reading, not by a missing number


def test_rehearsal_of_the_cell_is_correct(bench, capsys):
    rc = bench["run"].main([
        "--pair", "cicflow_gbt:fit_full", "--seed", "2147483999",
        "--seconds", "0.2", "--trace", "0", "--rehearse-cpu",
    ])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"setup_s", "fit_s"}
