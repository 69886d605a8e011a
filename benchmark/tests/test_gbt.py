"""The boosted one-vs-rest kind under the harness's own decision
(``run.judge``), at test size on the CPU: the reference in the program's
place is accepted, the bfloat16 control is refused, an unbroken run is
correct and every planted fault is refused.

    python3 -m pytest benchmark/tests -q      (CPU; not part of tier-1)
"""

import json

import pytest

import faults_gbt
import gen
import run

PAIR = "cicflow_gbt:fit_full"
SEEDS = (3, 2147483700, 77)


@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_refused_and_reference_accepted(seed):
    cell, cfg, traffic = run.resolve_pair(*PAIR.split(":"))
    adapter = run.load_module("estimators", cfg["estimator"])
    columns = gen.generate_columns(int(cfg["rehearse_rows"]), seed)
    s = run.model_seed(seed)
    limits = cfg["limits"]["fit"]
    for control, accepted in (("f32", True), ("bf16", False)):
        numbers = adapter.compare(
            "fit", adapter.control_product("fit", cfg, columns, s, control),
            cfg, columns, s,
        )
        assert run.judge(numbers, limits)[0] is accepted, (control, numbers)


def _last_line(capsys, seed=5):
    rc = run.main(["--pair", PAIR, "--seed", str(seed), "--seconds", "0.2",
                   "--trace", "0", "--rehearse-cpu"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_unbroken_run_is_correct(capsys):
    res = _last_line(capsys)
    assert res["correct"] is True, res["checks"]
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", sorted(faults_gbt.FAULTS))
def test_planted_fault_is_refused(fault, capsys):
    with faults_gbt.FAULTS[fault](run, "fit", "gbt"):
        res = _last_line(capsys)
    assert res["correct"] is False, res["checks"]
