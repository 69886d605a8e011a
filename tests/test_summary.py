"""Training-summary parity (SURVEY.md §5.5) + evaluator Params system +
tuning-spec persistence (Spark ``CrossValidatorModel.save`` round-trip)."""

import gc
import weakref

import numpy as np
import pytest

from sntc_tpu.core.frame import Frame
from sntc_tpu.evaluation import (
    BinaryClassificationEvaluator,
    MulticlassClassificationEvaluator,
)
from sntc_tpu.models import LogisticRegression, MultilayerPerceptronClassifier
from sntc_tpu.models.summary import (
    BinaryClassificationTrainingSummary,
    ClassificationTrainingSummary,
)
from sntc_tpu.tuning import (
    CrossValidator,
    CrossValidatorModel,
    ParamGridBuilder,
    TrainValidationSplit,
    TrainValidationSplitModel,
)


@pytest.fixture(scope="module")
def binary_frame():
    rng = np.random.default_rng(0)
    n = 1200
    X = rng.normal(size=(n, 5)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.normal(size=n) > 0).astype(
        np.float64
    )
    return Frame({"features": X, "label": y})


@pytest.fixture(scope="module")
def multi_frame():
    rng = np.random.default_rng(1)
    n = 1500
    X = rng.normal(size=(n, 5)).astype(np.float32)
    y = np.clip(np.floor(X[:, 0] * 1.5 + 1.5), 0, 2).astype(np.float64)
    return Frame({"features": X, "label": y})


def test_binary_lr_training_summary(mesh8, binary_frame):
    m = LogisticRegression(mesh=mesh8, maxIter=30).fit(binary_frame)
    s = m.summary
    assert isinstance(s, BinaryClassificationTrainingSummary)
    assert s.totalIterations > 0 and len(s.objectiveHistory) > 1
    # predictions frame: lazy, one per summary, carries the model's cols
    preds = s.predictions
    assert preds.num_rows == binary_frame.num_rows
    assert "prediction" in preds.columns and "probability" in preds.columns
    # per-class metrics agree with the evaluator on the same frame
    ev = MulticlassClassificationEvaluator(
        metricName="accuracy", mesh=mesh8
    )
    assert s.accuracy == pytest.approx(ev.evaluate(preds))
    assert s.precisionByLabel.shape == (2,)
    assert s.recallByLabel.shape == (2,)
    assert np.all(s.fMeasureByLabel() <= 1.0)
    assert s.weightedRecall == pytest.approx(s.accuracy)
    assert s.labels.tolist() == [0.0, 1.0]
    # threshold curves
    auc_ev = BinaryClassificationEvaluator().evaluate(preds)
    assert s.areaUnderROC == pytest.approx(auc_ev)
    roc = s.roc
    assert roc["FPR"][0] == 0.0 and roc["TPR"][-1] == 1.0
    assert np.all(np.diff(roc["FPR"]) >= -1e-12)
    pr = s.pr
    # roc carries both (0,0) and (1,1) anchors; pr prepends one point
    assert pr.num_rows == roc.num_rows - 1
    f_thr = s.fMeasureByThreshold()
    assert f_thr.num_rows > 1
    assert float(np.max(f_thr["metric"])) <= 1.0


def test_multinomial_lr_and_mlp_summary(mesh8, multi_frame):
    m = LogisticRegression(
        mesh=mesh8, maxIter=30, family="multinomial"
    ).fit(multi_frame)
    s = m.summary
    assert isinstance(s, ClassificationTrainingSummary)
    assert not isinstance(s, BinaryClassificationTrainingSummary)
    assert s.precisionByLabel.shape == (3,)
    assert 0.0 < s.accuracy <= 1.0

    mlp = MultilayerPerceptronClassifier(
        mesh=mesh8, layers=[5, 8, 3], maxIter=25, seed=0
    ).fit(multi_frame)
    s2 = mlp.summary
    assert isinstance(s2, ClassificationTrainingSummary)
    assert s2.totalIterations > 0
    assert s2.recallByLabel.shape == (3,)


def test_linear_svc_training_summary(mesh8, binary_frame):
    from sntc_tpu.models import LinearSVC

    m = LinearSVC(mesh=mesh8, maxIter=25).fit(binary_frame)
    s = m.summary
    assert isinstance(s, BinaryClassificationTrainingSummary)
    assert s.totalIterations > 0
    assert s.precisionByLabel.shape == (2,)
    assert 0.5 < s.areaUnderROC <= 1.0


def test_tree_classifier_summaries(mesh8, binary_frame, multi_frame):
    from sntc_tpu.models import GBTClassifier, RandomForestClassifier

    rf = RandomForestClassifier(
        mesh=mesh8, numTrees=4, maxDepth=4, seed=0
    ).fit(multi_frame)
    s = rf.summary
    assert isinstance(s, ClassificationTrainingSummary)
    assert s.objectiveHistory == [] and s.totalIterations == 0
    assert s.precisionByLabel.shape == (3,)
    assert 0.0 < s.accuracy <= 1.0

    gbt = GBTClassifier(
        mesh=mesh8, maxIter=5, maxDepth=3, seed=0
    ).fit(binary_frame)
    s2 = gbt.summary
    assert isinstance(s2, BinaryClassificationTrainingSummary)
    assert s2.totalIterations == 5
    assert 0.5 < s2.areaUnderROC <= 1.0


@pytest.mark.parametrize("solver", ("l-bfgs", "gd"))
def test_a_dropped_perceptron_dies_without_the_cyclic_collector(
    mesh8, multi_frame, solver
):
    """The perceptron's training summary keeps a copy of its model, not the
    model: its ``summary`` closes no cycle, so a model dropped by its
    caller frees its summary's frame (the scaler's device-resident features
    among it) at once, not when the cyclic collector next runs."""
    m = MultilayerPerceptronClassifier(
        mesh=mesh8, layers=[5, 6, 3], maxIter=5, seed=0, solver=solver
    ).fit(multi_frame)
    assert 0.0 < m.summary.accuracy <= 1.0
    ref = weakref.ref(m)
    gc.collect()
    gc.disable()
    try:
        del m
        assert ref() is None
    finally:
        gc.enable()


def test_model_evaluate(mesh8, binary_frame, multi_frame):
    m = LogisticRegression(mesh=mesh8, maxIter=20).fit(binary_frame)
    s = m.evaluate(binary_frame)
    assert not hasattr(s, "objectiveHistory")
    assert s.areaUnderROC == pytest.approx(m.summary.areaUnderROC)
    mlp = MultilayerPerceptronClassifier(
        mesh=mesh8, layers=[5, 6, 3], maxIter=15, seed=0
    ).fit(multi_frame)
    assert 0.0 < mlp.evaluate(multi_frame).accuracy <= 1.0


def test_evaluator_params_system():
    ev = MulticlassClassificationEvaluator(metricName="logLoss", beta=2.0)
    assert ev.getMetricName() == "logLoss"
    assert ev.getBeta() == 2.0
    assert "metricName" in ev.paramValues()
    assert "metricName" in ev.explainParams()
    with pytest.raises(ValueError):
        MulticlassClassificationEvaluator(metricName="nope")
    with pytest.raises(ValueError, match="metricLabel"):
        MulticlassClassificationEvaluator(metricLabel=-1.0)
    ev2 = ev.copy({"metricName": "accuracy"})
    assert ev2.getMetricName() == "accuracy"
    assert ev.getMetricName() == "logLoss"
    with pytest.raises(ValueError):
        BinaryClassificationEvaluator(metricName="nope")


def test_evaluator_save_load(tmp_path):
    from sntc_tpu.mlio import load_model, save_model

    ev = MulticlassClassificationEvaluator(
        metricName="fMeasureByLabel", metricLabel=2.0, beta=0.5,
        weightCol="w",
    )
    loaded = load_model(save_model(ev, str(tmp_path / "ev")))
    assert isinstance(loaded, MulticlassClassificationEvaluator)
    assert loaded.paramValues() == ev.paramValues()


def test_cross_validator_model_persists_spec(mesh8, binary_frame, tmp_path):
    from sntc_tpu.mlio import load_model, save_model

    grid = (
        ParamGridBuilder()
        .addGrid("regParam", [0.0, 0.1])
        .build()
    )
    cv = CrossValidator(
        estimator=LogisticRegression(mesh=mesh8, maxIter=15),
        estimatorParamMaps=grid,
        evaluator=BinaryClassificationEvaluator(),
        numFolds=2,
        seed=0,
    )
    cvm = cv.fit(binary_frame)
    loaded = load_model(save_model(cvm, str(tmp_path / "cvm")))
    assert isinstance(loaded, CrossValidatorModel)
    assert loaded.avgMetrics == pytest.approx(cvm.avgMetrics)
    assert loaded.bestIndex == cvm.bestIndex
    assert loaded.estimatorParamMaps == grid
    assert isinstance(loaded.estimator, LogisticRegression)
    assert isinstance(loaded.evaluator, BinaryClassificationEvaluator)
    # the restored spec is runnable: re-scoring the best model's transform
    # with the restored evaluator reproduces the recorded metric's scale
    out = loaded.transform(binary_frame)
    assert 0.5 < loaded.evaluator.evaluate(out) <= 1.0
    # and the loaded ESTIMATOR still fits
    refit = loaded.estimator.copy(
        loaded.estimatorParamMaps[loaded.bestIndex]
    ).fit(binary_frame)
    a = refit.transform(binary_frame)["prediction"]
    b = out["prediction"]
    assert np.mean(a == b) > 0.99


def test_cross_validator_estimator_save_load(mesh8, tmp_path):
    from sntc_tpu.mlio import load_model, save_model

    grid = ParamGridBuilder().addGrid("regParam", [0.0, 0.5]).build()
    cv = CrossValidator(
        estimator=LogisticRegression(mesh=mesh8, maxIter=10),
        estimatorParamMaps=grid,
        evaluator=MulticlassClassificationEvaluator(metricName="accuracy"),
        numFolds=2,
    )
    loaded = load_model(save_model(cv, str(tmp_path / "cv")))
    assert isinstance(loaded, CrossValidator)
    assert loaded.getNumFolds() == 2
    assert loaded.estimatorParamMaps == grid
    assert loaded.evaluator.getMetricName() == "accuracy"


def test_tvs_model_persists_spec(mesh8, binary_frame, tmp_path):
    from sntc_tpu.mlio import load_model, save_model

    grid = ParamGridBuilder().addGrid("maxIter", [5, 15]).build()
    tvs = TrainValidationSplit(
        estimator=LogisticRegression(mesh=mesh8),
        estimatorParamMaps=grid,
        evaluator=BinaryClassificationEvaluator(),
        trainRatio=0.7,
        seed=0,
    )
    m = tvs.fit(binary_frame)
    loaded = load_model(save_model(m, str(tmp_path / "tvsm")))
    assert isinstance(loaded, TrainValidationSplitModel)
    assert loaded.validationMetrics == pytest.approx(m.validationMetrics)
    assert loaded.estimatorParamMaps == grid
    assert isinstance(loaded.evaluator, BinaryClassificationEvaluator)
