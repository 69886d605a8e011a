import numpy as np
import pytest

from sntc_tpu.core.frame import Frame
from sntc_tpu.evaluation import MulticlassClassificationEvaluator
from sntc_tpu.mlio import load_model, save_model
from sntc_tpu.models import LogisticRegression
from sntc_tpu.tuning import (
    CrossValidator,
    ParamGridBuilder,
    TrainValidationSplit,
)


def test_param_grid_builder():
    grid = (
        ParamGridBuilder()
        .addGrid("regParam", [0.0, 0.1])
        .addGrid("maxIter", [10, 20, 30])
        .baseOn(tol=1e-4)
        .build()
    )
    assert len(grid) == 6
    assert all(g["tol"] == 1e-4 for g in grid)
    assert {(g["regParam"], g["maxIter"]) for g in grid} == {
        (r, m) for r in (0.0, 0.1) for m in (10, 20, 30)
    }
    assert ParamGridBuilder().build() == [{}]


def _data(n=1200, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 5)).astype(np.float32)
    y = (X[:, 0] + 0.5 * rng.normal(size=n) > 0).astype(np.float64)
    return Frame({"features": X, "label": y})


def test_cross_validator_picks_better_config(mesh8):
    f = _data()
    # regParam=10 cripples the model; CV must prefer the small one
    grid = ParamGridBuilder().addGrid("regParam", [1e-4, 10.0]).build()
    cv = CrossValidator(
        estimator=LogisticRegression(mesh=mesh8, maxIter=30),
        estimatorParamMaps=grid,
        evaluator=MulticlassClassificationEvaluator(metricName="accuracy", mesh=mesh8),
        numFolds=3,
        seed=1,
    )
    model = cv.fit(f)
    assert model.bestIndex == 0
    assert len(model.avgMetrics) == 2
    assert model.avgMetrics[0] > model.avgMetrics[1]
    out = model.transform(f)
    assert (out["prediction"] == f["label"]).mean() > 0.85


def test_cross_validator_collect_sub_models(mesh8):
    f = _data(400)
    cv = CrossValidator(
        estimator=LogisticRegression(mesh=mesh8, maxIter=10),
        estimatorParamMaps=[{}],
        evaluator=MulticlassClassificationEvaluator(metricName="accuracy", mesh=mesh8),
        numFolds=2,
        collectSubModels=True,
    )
    model = cv.fit(f)
    assert len(model.subModels) == 1 and len(model.subModels[0]) == 2


def test_train_validation_split(mesh8, tmp_path):
    f = _data(seed=2)
    grid = ParamGridBuilder().addGrid("regParam", [1e-4, 10.0]).build()
    tvs = TrainValidationSplit(
        estimator=LogisticRegression(mesh=mesh8, maxIter=30),
        estimatorParamMaps=grid,
        evaluator=MulticlassClassificationEvaluator(metricName="accuracy", mesh=mesh8),
        trainRatio=0.7,
        seed=3,
    )
    model = tvs.fit(f)
    assert model.bestIndex == 0
    assert len(model.validationMetrics) == 2
    # best-model persistence through the generic sub-stage mechanism
    save_model(model, str(tmp_path / "tvs"))
    loaded = load_model(str(tmp_path / "tvs"))
    np.testing.assert_array_equal(
        loaded.transform(f)["prediction"], model.transform(f)["prediction"]
    )


def test_utils_metrics_logger(tmp_path):
    from sntc_tpu.obs import SpanTracer
    from sntc_tpu.utils import MetricsLogger

    log = MetricsLogger(str(tmp_path / "m.jsonl"))
    log.log(event="fit_start", model="lr")
    log.log(event="fit_end", loss=0.5)
    records = log.read_all()
    assert [r["step"] for r in records] == [0, 1]
    assert records[1]["loss"] == 0.5

    # phase timing lives on the obs span tracer now (the old StepTimer
    # was dormant telemetry and is gone)
    t = SpanTracer(capacity=8)
    with t.span("a"):
        pass
    with t.span("a"):
        pass
    assert [s["name"] for s in t.spans()] == ["a", "a"]


def test_cross_validator_fold_col(mesh8):
    f = _data(n=400, seed=3)
    folds = (np.arange(400) % 3).astype(np.float64)
    f = f.with_column("myfold", folds)
    cv = CrossValidator(
        estimator=LogisticRegression(mesh=mesh8, maxIter=20),
        estimatorParamMaps=ParamGridBuilder().addGrid("regParam", [0.0, 0.1]).build(),
        evaluator=MulticlassClassificationEvaluator(metricName="accuracy", mesh=mesh8),
        numFolds=3, foldCol="myfold",
    ).fit(f)
    assert len(cv.avgMetrics) == 2
    with pytest.raises(ValueError, match="foldCol"):
        CrossValidator(
            estimator=LogisticRegression(mesh=mesh8),
            evaluator=MulticlassClassificationEvaluator(mesh=mesh8),
            numFolds=2, foldCol="myfold",
        ).fit(f)  # fold index 2 out of range for numFolds=2


def test_tvs_collect_sub_models(mesh8):
    f = _data(n=300, seed=4)
    tvs = TrainValidationSplit(
        estimator=LogisticRegression(mesh=mesh8, maxIter=20),
        estimatorParamMaps=ParamGridBuilder().addGrid("regParam", [0.0, 0.05]).build(),
        evaluator=MulticlassClassificationEvaluator(metricName="accuracy", mesh=mesh8),
        collectSubModels=True,
    ).fit(f)
    assert tvs.subModels is not None and len(tvs.subModels) == 2


def test_cross_validator_fold_col_rejects_empty_and_fractional(mesh8):
    f = _data(n=90, seed=5)
    ev = MulticlassClassificationEvaluator(metricName="accuracy", mesh=mesh8)
    est = LogisticRegression(mesh=mesh8, maxIter=10)
    with pytest.raises(ValueError, match="empty"):
        CrossValidator(
            estimator=est, evaluator=ev, numFolds=3,
            foldCol="z",
        ).fit(f.with_column("z", np.zeros(90)))  # folds 1,2 empty
    with pytest.raises(ValueError, match="integers"):
        CrossValidator(
            estimator=est, evaluator=ev, numFolds=2, foldCol="z",
        ).fit(f.with_column("z", np.full(90, 0.5)))


# ---------------------------------------------------------------------------
# batched (vmapped) grid fits — SURVEY.md §2.5 task parallelism
# ---------------------------------------------------------------------------


def _data15(n=1500, seed=3, k=4):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 6)).astype(np.float32)
    W = rng.normal(size=(6, k))
    y = np.argmax(X @ W + 0.3 * rng.normal(size=(n, k)), axis=1).astype(
        np.float64
    )
    return Frame({"features": X, "label": y})


def test_supports_batched_grid_rules(mesh8):
    lr = LogisticRegression(mesh=mesh8, maxIter=10)
    ok = [{"regParam": 0.0}, {"regParam": 0.1, "elasticNetParam": 0.5}]
    assert lr.supports_batched_grid(ok)
    # single point: nothing to batch
    assert not lr.supports_batched_grid([{"regParam": 0.1}])
    # non-uniform static knob
    assert not lr.supports_batched_grid(
        [{"maxIter": 5}, {"maxIter": 20}]
    )
    # uniform static knob is fine
    assert lr.supports_batched_grid(
        [{"maxIter": 5, "regParam": 0.0}, {"maxIter": 5, "regParam": 0.1}]
    )
    # unknown/unsupported key -> sequential fallback
    assert not lr.supports_batched_grid(
        [{"regParam": 0.0}, {"featuresCol": "other"}]
    )
    # bound constraints -> sequential fallback
    lb = np.full((1, 5), -1.0)
    bounded = LogisticRegression(
        mesh=mesh8, maxIter=10, lowerBoundsOnCoefficients=lb
    )
    assert not bounded.supports_batched_grid(ok)


def test_fit_grid_matches_individual_fits(mesh8):
    f = _data()
    lr = LogisticRegression(mesh=mesh8, maxIter=25)
    grid = (
        ParamGridBuilder()
        .addGrid("regParam", [0.0, 0.01, 0.1])
        .build()
    )
    batched = lr._fit_grid(f, grid)
    for params, bm in zip(grid, batched):
        sm = lr.copy(params).fit(f)
        np.testing.assert_allclose(
            bm.coefficientMatrix, sm.coefficientMatrix, atol=2e-3
        )
        np.testing.assert_allclose(
            bm.interceptVector, sm.interceptVector, atol=2e-3
        )
        # grid-point params land on the batched models too
        assert bm.getRegParam() == params["regParam"]


def test_fit_grid_mixed_l1_l2_groups(mesh8):
    """L1 (OWLQN) and L2 (LBFGS) points batch separately but return in
    grid order, matching their individual fits."""
    f = _data15()
    lr = LogisticRegression(mesh=mesh8, maxIter=20)
    grid = [
        {"regParam": 0.05, "elasticNetParam": 1.0},  # pure L1
        {"regParam": 0.0},                            # unregularized
        {"regParam": 0.05, "elasticNetParam": 0.0},   # pure L2
        {"regParam": 0.05, "elasticNetParam": 0.5},   # elastic net
    ]
    batched = lr._fit_grid(f, grid)
    assert len(batched) == 4
    for params, bm in zip(grid, batched):
        sm = lr.copy(params).fit(f)
        np.testing.assert_allclose(
            bm.coefficientMatrix, sm.coefficientMatrix, atol=5e-3
        )


def test_cross_validator_batched_matches_sequential(mesh8, monkeypatch):
    f = _data(800)
    grid = ParamGridBuilder().addGrid("regParam", [1e-4, 0.05, 5.0]).build()

    def run():
        cv = CrossValidator(
            estimator=LogisticRegression(mesh=mesh8, maxIter=20),
            estimatorParamMaps=grid,
            evaluator=MulticlassClassificationEvaluator(
                metricName="accuracy", mesh=mesh8
            ),
            numFolds=2,
            seed=5,
        )
        return cv.fit(f)

    monkeypatch.setenv("SNTC_TUNING_BATCH", "0")
    seq = run()
    monkeypatch.setenv("SNTC_TUNING_BATCH", "1")
    bat = run()
    assert bat.bestIndex == seq.bestIndex
    np.testing.assert_allclose(bat.avgMetrics, seq.avgMetrics, atol=1e-3)


def test_parallelism_noop_warns(mesh8, caplog):
    """Spark-ported code setting parallelism on a non-batchable estimator
    gets a warning, not silence."""
    import logging

    f = _data(300)
    grid = ParamGridBuilder().addGrid("maxIter", [5, 10]).build()  # static-varying
    cv = CrossValidator(
        estimator=LogisticRegression(mesh=mesh8),
        estimatorParamMaps=grid,
        evaluator=MulticlassClassificationEvaluator(
            metricName="accuracy", mesh=mesh8
        ),
        numFolds=2,
        parallelism=4,
    )
    with caplog.at_level(logging.WARNING, logger="sntc_tpu.tuning.cross_validator"):
        cv.fit(f)
    assert any("parallelism" in r.message for r in caplog.records)


def test_fit_grid_folds_matches_per_fold_fits(mesh8):
    """The one-program fold×grid sweep equals per-fold subset fits: a fold
    is a zero-weight mask, so coefficients must match fits on the actual
    row subsets (modulo f32 summation order)."""
    f = _data(900, seed=8)
    lr = LogisticRegression(mesh=mesh8, maxIter=20)
    grid = [{"regParam": 0.0}, {"regParam": 0.05, "elasticNetParam": 1.0}]
    rng = np.random.default_rng(3)
    fold_of = rng.integers(0, 3, size=f.num_rows)
    batched = lr._fit_grid_folds(f, grid, fold_of, 3)
    assert len(batched) == 3 and all(len(row) == 2 for row in batched)
    for fold in range(3):
        train = f.filter(fold_of != fold)
        for gi, params in enumerate(grid):
            ref = lr.copy(params).fit(train)
            np.testing.assert_allclose(
                batched[fold][gi].coefficientMatrix,
                ref.coefficientMatrix,
                atol=5e-3,
            )


def test_ovr_lr_vectorized_matches_sequential(mesh8):
    """OneVsRest(LogisticRegression) runs all K binary fits as one vmapped
    program; models must match the sequential per-class fits."""
    from sntc_tpu.models import OneVsRest

    f = _data15(1200, seed=6, k=4)
    base = LogisticRegression(mesh=mesh8, maxIter=25, regParam=1e-3)
    calls = []
    orig = LogisticRegression._fit_ovr_lanes

    def spy(self, *a, **kw):
        calls.append(1)
        return orig(self, *a, **kw)

    LogisticRegression._fit_ovr_lanes = spy
    try:
        vec = OneVsRest(classifier=base, mesh=mesh8).fit(f)
    finally:
        LogisticRegression._fit_ovr_lanes = orig
    assert calls, "vectorized OvR path did not run (gate regressed?)"
    assert len(vec.models) == 4

    # sequential reference: force family=binomial-incompatible gate off
    seq_models = []
    y = np.asarray(f["label"])
    for c in range(4):
        sub = f.with_column("bin", (y == c).astype(np.float64))
        seq_models.append(
            base.copy({"labelCol": "bin"}).fit(sub)
        )
    for vm, sm in zip(vec.models, seq_models):
        np.testing.assert_allclose(
            vm.coefficientMatrix, sm.coefficientMatrix, atol=5e-3
        )
    out = vec.transform(f)
    assert (out["prediction"] == y).mean() > 0.8
