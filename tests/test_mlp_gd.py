"""The perceptron's fit under Spark's ``gd`` solver against the benchmark's
plain reference (``benchmark/reference_mlp_gd.py``, which imports nothing
from ``sntc_tpu``), at a small size on the CPU, through ``Pipeline.fit`` as
the cell ``cicflow_mlp_gd.fit`` builds it: the loss at every iterate, the
final weights and the step count agree; a large ``tol`` stops both at the
same step; the float32 head multiplies at HIGHEST and the bfloat16 one does
not; the evaluation counter rises by the fit's steps; the harness's CPU
rehearsal of the cell is ``correct`` and the bfloat16 control is refused."""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"
)
SEEDS = (3, 2147483700, 77)
ROWS = 6000


@pytest.fixture(scope="module")
def bench():
    """The harness's modules, importable the way ``run.py`` imports them."""
    for p in (_BENCH, os.path.dirname(_BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import gen
    import reference_mlp_gd
    import run

    cell, cfg, _ = run.resolve_pair("cicflow_mlp_gd", "fit_full")
    adapter = run.load_module("estimators", cfg["estimator"])
    return {"run": run, "gen": gen, "ref": reference_mlp_gd, "cfg": cfg,
            "adapter": adapter, "limits": cfg["limits"]["fit"]}


@pytest.fixture(scope="module")
def frames(bench):
    return {s: bench["gen"].generate_columns(ROWS, s) for s in SEEDS}


def _fit_both(bench, columns, seed, mesh, **over):
    """The program's fitted head (through the cell's ``Pipeline``) and the
    reference's ``(losses, theta, steps, converged)`` under ``cfg | over``."""
    run, adapter = bench["run"], bench["adapter"]
    cfg = dict(bench["cfg"], **over)
    s = run.model_seed(seed)
    model = adapter.build_pipeline(cfg, mesh, s).fit(
        run.fresh_frame(columns)
    )
    from estimators import mlp as base

    _, y, X, mean, std = base._prepared("fit", columns)
    ref = bench["ref"].fit(X, y, mean, std, cfg,
                           adapter.initial_weights(cfg, s))
    return model.getStages()[-1], ref[:4]


@pytest.mark.parametrize("chips", (1, 8))
@pytest.mark.parametrize("seed", SEEDS)
def test_gd_fit_follows_the_reference(bench, frames, seed, chips, mesh8):
    from sntc_tpu.parallel.mesh import default_mesh

    mesh = mesh8 if chips == 8 else default_mesh(1)
    head, (losses, theta, steps, converged) = _fit_both(
        bench, frames[seed], seed, mesh, maxIter=10
    )
    assert head.summary.totalIterations == steps == 10
    assert not converged
    hist = np.asarray(head.summary.objectiveHistory, np.float64)
    assert hist.shape == (11,)
    np.testing.assert_allclose(hist, losses, rtol=2e-6)
    assert losses[-1] < losses[0]
    moved = np.linalg.norm(theta - bench["adapter"].initial_weights(
        bench["cfg"], bench["run"].model_seed(seed)))
    assert moved > 0.01 and head.weights.shape == theta.shape
    assert np.max(np.abs(head.weights - theta)) < 1e-5 * moved


@pytest.mark.parametrize("tol", (0.02, 0.005))
def test_a_large_tol_stops_both_at_the_same_step(bench, frames, tol):
    """Spark's test first compares the second step's weights with the
    first's: at ``tol`` 0.02 both stop after 2 steps, at 0.005 after 8-9."""
    from sntc_tpu.parallel.mesh import default_mesh

    seed = SEEDS[0]
    head, (losses, _, steps, converged) = _fit_both(
        bench, frames[seed], seed, default_mesh(1), tol=tol
    )
    assert converged
    assert steps == 2 if tol == 0.02 else 3 <= steps <= 15
    assert head.summary.totalIterations == steps
    np.testing.assert_allclose(head.summary.objectiveHistory, losses,
                               rtol=2e-6)


def _optimize(dtype, tol=1e-6, max_iter=3, lower=False, solver="gd"):
    from sntc_tpu.models.mlp import _mlp_optimize, _n_weights

    layers = (6, 5, 3)
    rng = np.random.default_rng(0)
    args = (jnp.asarray(rng.standard_normal((64, 6)), jnp.float32),
            jnp.asarray(rng.integers(0, 3, 64), jnp.int32),
            jnp.ones(64, jnp.float32),
            jnp.asarray(rng.uniform(-0.5, 0.5, _n_weights(layers)),
                        jnp.float32),
            None, jnp.asarray(max_iter, jnp.int32))
    kw = dict(layers=layers, max_iter=max_iter, tol=tol, solver=solver,
              step_size=0.03, compute_dtype=jnp.dtype(dtype))
    return (_mlp_optimize.lower(*args, **kw) if lower
            else _mlp_optimize(*args, **kw))


def test_converged_and_steps_are_reported_as_they_happened():
    res, _ = _optimize("float32", tol=10.0, max_iter=7)
    assert bool(res.converged) and int(res.n_iters) == 2
    hist = np.asarray(res.history)
    assert hist.shape == (8,) and np.all(hist[2:] == float(res.loss))
    res, _ = _optimize("float32", tol=1e-12, max_iter=7)
    assert not bool(res.converged) and int(res.n_iters) == 7


def _dots(text):
    return [ln for ln in text.splitlines() if "dot_general" in ln]


@pytest.mark.parametrize("dtype", ("float32", "bfloat16"))
def test_float32_products_are_highest_and_bfloat16_ones_are_not(dtype):
    """Forward and backward: every ``dot_general`` of the float32 fit
    carries HIGHEST (autodiff keeps the forward's precision), none of the
    bfloat16 fit's does."""
    dots = _dots(_optimize(dtype, lower=True).as_text())
    assert len(dots) >= 6  # 2 forward, 2 + 2 backward, 1 final forward
    highest = [ln for ln in dots if "HIGHEST" in ln]
    assert len(highest) == (len(dots) if dtype == "float32" else 0), dots


@pytest.mark.parametrize("dtype", ("float32", "bfloat16"))
@pytest.mark.parametrize("solver", ("gd", "l-bfgs"))
def test_the_loss_picks_labels_without_a_gather(solver, dtype):
    """The lowered fit holds no gather op, and the compiled one neither a
    gather nor a scatter instruction: a per-row pick lowers to a serial
    gather on a TPU, and its gradient to a scatter-add.  (The StableHLO
    keeps the scalar-indexed ``.at[i].set`` of the loss history and of
    L-BFGS's pairs as scatters, which XLA compiles to
    ``dynamic-update-slice``.)  Ops are matched, not substrings: the
    metadata carries source file names."""
    low = _optimize(dtype, lower=True, solver=solver)
    assert not re.search(r"\bstablehlo\.gather\b", low.as_text())
    compiled = low.compile().as_text()
    assert not re.search(r"\s(gather|scatter)\(", compiled), [
        ln for ln in compiled.splitlines() if re.search(r"(gather|scatter)\(", ln)
    ]


def _nll(margins, ys, ws, pick):
    logp = jax.nn.log_softmax(margins, axis=1)
    return -jnp.sum(ws * pick(logp, ys)) / jnp.sum(ws)


def _gather_pick(logp, ys):
    return jnp.take_along_axis(logp, ys[:, None], axis=1)[:, 0]


@pytest.mark.parametrize("case", ("large_margins", "zero_weights",
                                  "absent_class"))
def test_the_masked_pick_is_the_gather_bit_for_bit(case):
    """Loss and gradient, with respect to the margins and to ``theta``
    through ``_forward``, equal the ``take_along_axis`` form to the bit."""
    from sntc_tpu.models.mlp import _forward, _label_log_prob, _n_weights

    layers, n = (6, 5, 15), 4096
    rng = np.random.default_rng(40)
    margins = rng.standard_normal((n, 15)).astype(np.float32) * 5
    ys = rng.integers(0, 15, n)
    ws = rng.uniform(0.5, 2.0, n).astype(np.float32)
    if case == "large_margins":
        hit = rng.random((n, 15)) < 0.1
        margins[hit] = rng.choice([-80.0, 80.0], int(hit.sum()))
    elif case == "zero_weights":
        ws[-300:] = 0.0  # padded rows
    else:
        ys[ys == 7] = 8
    margins, ys, ws = jnp.asarray(margins), jnp.asarray(ys, jnp.int32), \
        jnp.asarray(ws)

    def by(pick):
        return jax.value_and_grad(_nll)(margins, ys, ws, pick)

    for got, want in zip(by(_label_log_prob), by(_gather_pick)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    X = jnp.asarray(rng.standard_normal((n, 6)), jnp.float32)
    theta = jnp.asarray(rng.uniform(-2, 2, _n_weights(layers)), jnp.float32)

    def through_forward(pick):
        return jax.value_and_grad(
            lambda t: _nll(_forward(t, X, layers), ys, ws, pick)
        )(theta)

    for got, want in zip(through_forward(_label_log_prob),
                         through_forward(_gather_pick)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("program", ("_mlp_margins", "_mlp_predict_fused",
                                     "_mlp_serve"))
def test_predict_products_are_highest(program):
    from sntc_tpu.models import mlp

    layers = (6, 5, 3)
    theta = jnp.zeros(mlp._n_weights(layers), jnp.float32)
    X = jnp.zeros((8, 6), jnp.float32)
    fn = getattr(mlp, program)
    if program == "_mlp_serve":
        low = fn.lower(theta, X, jnp.zeros(3, jnp.float32), layers=layers,
                       mode="thresholds")
    else:
        low = fn.lower(theta, X, layers)
    dots = _dots(low.as_text())
    assert len(dots) == 2 and all("HIGHEST" in ln for ln in dots), dots


def test_grad_evals_counter_rises_by_the_fits_steps(bench, frames):
    from sntc_tpu.obs import registry
    from sntc_tpu.parallel.mesh import default_mesh

    def counted():
        return registry().get("sntc_mlp_grad_evals_total") or 0

    seed, run, adapter = SEEDS[1], bench["run"], bench["adapter"]
    before = counted()
    _fit_both(bench, frames[seed], seed, default_mesh(1), maxIter=7)
    assert counted() - before == 7
    cfg = dict(bench["cfg"], solver="l-bfgs", maxIter=3)
    adapter.build_pipeline(cfg, default_mesh(1), run.model_seed(seed)).fit(
        run.fresh_frame(frames[seed]))
    assert counted() - before == 7  # L-BFGS returns no count


def test_a_head_that_multiplies_in_bfloat16_is_refused(bench, monkeypatch):
    from sntc_tpu.models import mlp

    forward = mlp._forward
    monkeypatch.setattr(mlp, "_forward", lambda theta, X, layers, dt=None:
                        forward(theta, X, layers, jnp.bfloat16))
    monkeypatch.setattr(bench["adapter"], "_STATED", [])
    jax.clear_caches()
    try:
        with pytest.raises(RuntimeError, match="not the stated precision"):
            bench["adapter"].require_stated_products(bench["cfg"])
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    bench["adapter"].require_stated_products(bench["cfg"])


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
    assert "loss0_gap" in over, checks  # refused by a reading


def test_rehearsal_of_the_cell_is_correct(bench, capsys):
    rc = bench["run"].main([
        "--workload", "cicflow_mlp_gd.fit", "--seed", "3900000123",
        "--seconds", "0.2", "--trace", "0", "--rehearse-cpu",
    ])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"setup_s", "fit_s"}
    assert set(line["checks"]) == set(bench["limits"])
