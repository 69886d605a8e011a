"""The controls, at a size a test run can hold: the reference put in the
program's place one step of precision down has to come out NOT correct under
the configuration's limits, by the harness's own decision (``run.judge``),
and the reference itself has to come out correct.  Cells are named by
configuration and mix, so those the manifest does not hold yet are held to
the same.

    python3 -m pytest benchmark/tests -q      (CPU; not part of tier-1)
"""

import pytest

import gen
import run

CASES = [
    ("cicflow_rf", "fit_full", "bf16"),
    ("cicflow_mlp", "fit_full", "bf16"),
    ("cicflow_mlp", "evaluate_full", "bf16"),
]
SEEDS = (3, 2147483700, 77)


@pytest.mark.parametrize("config,mix,control", CASES)
@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_refused_and_reference_accepted(config, mix, control, seed):
    cell, cfg, traffic = run.resolve_pair(config, mix)
    adapter = run.load_module("estimators", cfg["estimator"])
    kind = traffic["kind"]
    columns = gen.generate_columns(int(cfg["rehearse_rows"]), seed)
    s = run.model_seed(seed)
    limits = cfg["limits"][kind]
    good = adapter.compare(
        kind, adapter.control_product(kind, cfg, columns, s, "f32"),
        cfg, columns, s,
    )
    assert run.judge(good, limits)[0], good
    bad = adapter.compare(
        kind, adapter.control_product(kind, cfg, columns, s, control),
        cfg, columns, s,
    )
    assert not run.judge(bad, limits)[0], bad
