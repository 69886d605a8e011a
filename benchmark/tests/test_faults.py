"""Drive the rest of a run (``run.main`` past its look for a chip, on the
CPU at test size) with the timed path broken underneath, and see ``correct``
come out false: once for each fault the cell can have; and true unbroken."""

import json

import pytest

import faults
import run

PAIRS = {
    "cicflow_rf:fit_full": ("fit", "rf"),
    "cicflow_mlp:fit_full": ("fit", "mlp"),
    "cicflow_mlp:evaluate_full": ("evaluate", "mlp"),
}
CASES = [(p, f) for p, ke in PAIRS.items() for f in faults.APPLICABLE[ke]]
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _last_line(capsys, pair, seed=5):
    rc = run.main(["--pair", pair, "--seed", str(seed),
                   "--seconds", "0.2", "--trace", "0", "--rehearse-cpu"])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(line)


@pytest.mark.parametrize("pair", sorted(PAIRS))
def test_unbroken_run_is_correct(pair, capsys):
    res = _last_line(capsys, pair)
    assert res["correct"] is True, res["checks"]
    assert list(res) == LINE_KEYS
    assert res["device"]["platform"] == "cpu"
    assert "setup_s" in res["metrics"] and len(res["metrics"]) == 2


@pytest.mark.parametrize("pair,fault", CASES)
def test_planted_fault_is_refused(pair, fault, capsys):
    kind, estimator = PAIRS[pair]
    with faults.FAULTS[fault](run, kind, estimator):
        res = _last_line(capsys, pair)
    assert res["correct"] is False, res["checks"]
