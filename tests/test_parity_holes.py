"""Round-2 parity closures: LR bound-constrained fit,
per-class ``thresholds``, multiclass-evaluator ``weightCol``."""

import numpy as np
import pytest

from sntc_tpu.core.frame import Frame
from sntc_tpu.evaluation.multiclass import MulticlassClassificationEvaluator
from sntc_tpu.models.logistic_regression import LogisticRegression


def _binary(n=3000, d=5, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    true_w = np.array([1.5, -2.0, 0.8, 0.0, -0.5])
    p = 1.0 / (1.0 + np.exp(-(X @ true_w + 0.3)))
    y = (rng.random(n) < p).astype(np.float64)
    return Frame({"features": X, "label": y}), X, y


# ---------------------------------------------------------------------------
# bound-constrained LR
# ---------------------------------------------------------------------------


def test_lr_nonnegative_bounds_respected_and_optimal():
    frame, X, y = _binary()
    d = X.shape[1]
    lr = LogisticRegression(
        maxIter=200, regParam=0.0, tol=1e-9,
        lowerBoundsOnCoefficients=np.zeros((1, d)),
    )
    model = lr.fit(frame)
    coef = model.coefficients
    assert (coef >= -1e-5).all()
    # constrained optimum from scipy L-BFGS-B on the same objective
    from scipy.optimize import minimize

    def obj(theta):
        w, b = theta[:d], theta[d]
        z = X @ w + b
        return float(np.mean(np.logaddexp(0.0, z) - y * z))

    res = minimize(
        obj,
        np.zeros(d + 1),
        method="L-BFGS-B",
        bounds=[(0, None)] * d + [(None, None)],
    )
    ours = obj(np.concatenate([coef, [model.intercept]]))
    assert ours == pytest.approx(res.fun, abs=2e-4)


def test_lr_interval_bounds_multinomial():
    rng = np.random.default_rng(3)
    n, d, k = 2000, 4, 3
    X = rng.normal(size=(n, d)).astype(np.float32)
    W = rng.normal(size=(d, k))
    y = np.argmax(X @ W + 0.3 * rng.normal(size=(n, k)), axis=1).astype(
        np.float64
    )
    frame = Frame({"features": X, "label": y})
    lb = np.full((k, d), -0.5)
    ub = np.full((k, d), 0.5)
    model = LogisticRegression(
        maxIter=100, family="multinomial",
        lowerBoundsOnCoefficients=lb, upperBoundsOnCoefficients=ub,
        lowerBoundsOnIntercepts=np.full(k, -0.1),
        upperBoundsOnIntercepts=np.full(k, 0.1),
    ).fit(frame)
    assert (model.coefficientMatrix >= -0.5 - 1e-5).all()
    assert (model.coefficientMatrix <= 0.5 + 1e-5).all()
    assert (np.abs(model.interceptVector) <= 0.1 + 1e-5).all()


def test_lr_bounds_reject_l1():
    frame, _, _ = _binary(n=200)
    lr = LogisticRegression(
        regParam=0.1, elasticNetParam=0.5,
        lowerBoundsOnCoefficients=np.zeros((1, 5)),
    )
    with pytest.raises(ValueError, match="L2"):
        lr.fit(frame)


def test_lr_bounds_shape_validation():
    frame, _, _ = _binary(n=200)
    lr = LogisticRegression(lowerBoundsOnCoefficients=np.zeros((2, 3)))
    with pytest.raises(ValueError, match="shape"):
        lr.fit(frame)


# ---------------------------------------------------------------------------
# per-class thresholds
# ---------------------------------------------------------------------------


def test_thresholds_scale_predictions():
    rng = np.random.default_rng(5)
    n, k = 500, 3
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = rng.integers(0, k, n).astype(np.float64)
    frame = Frame({"features": X, "label": y})
    model = LogisticRegression(maxIter=20, family="multinomial").fit(frame)
    base = model.transform(frame)["prediction"]
    # huge threshold on class 0 suppresses it entirely
    model.setThresholds([1e6, 1.0, 1.0])
    pred = model.transform(frame)["prediction"]
    assert not (pred == 0.0).any()
    # equal thresholds reproduce plain argmax
    model.setThresholds([1.0, 1.0, 1.0])
    np.testing.assert_array_equal(
        model.transform(frame)["prediction"], base
    )
    # zero threshold wins whenever its probability is positive
    model.setThresholds([0.0, 1.0, 1.0])
    assert (model.transform(frame)["prediction"] == 0.0).all()


def test_thresholds_validation():
    frame, _, _ = _binary(n=100)
    model = LogisticRegression(maxIter=5).fit(frame)
    model.setThresholds([0.5, 0.5, 0.5])
    with pytest.raises(ValueError, match="numClasses"):
        model.transform(frame)
    model.setThresholds([0.0, 0.0])
    with pytest.raises(ValueError, match="one zero"):
        model.transform(frame)


# ---------------------------------------------------------------------------
# evaluator weightCol
# ---------------------------------------------------------------------------


def test_multiclass_evaluator_weight_col():
    y = np.array([0, 0, 1, 1, 2], np.float64)
    p = np.array([0, 1, 1, 1, 0], np.float64)
    w = np.array([2.0, 1.0, 1.0, 3.0, 1.0])
    frame = Frame({"label": y, "prediction": p, "w": w})
    acc_w = MulticlassClassificationEvaluator(
        metricName="accuracy", weightCol="w"
    ).evaluate(frame)
    # weighted accuracy: correct rows weigh 2+1+3 of total 8
    assert acc_w == pytest.approx(6.0 / 8.0)
    acc = MulticlassClassificationEvaluator(metricName="accuracy").evaluate(
        frame
    )
    assert acc == pytest.approx(3.0 / 5.0)


def test_multiclass_evaluator_weighted_logloss():
    y = np.array([0, 1], np.float64)
    prob = np.array([[0.8, 0.2], [0.4, 0.6]])
    w = np.array([3.0, 1.0])
    frame = Frame({"label": y, "prediction": y, "probability": prob, "w": w})
    ev = MulticlassClassificationEvaluator(metricName="logLoss", weightCol="w")
    expect = (3.0 * -np.log(0.8) + 1.0 * -np.log(0.6)) / 4.0
    assert ev.evaluate(frame) == pytest.approx(expect)


# ---------------------------------------------------------------------------
# code-review regressions (round 2)
# ---------------------------------------------------------------------------


def test_string_indexer_nan_roundtrips_through_fit_vocab():
    from sntc_tpu.feature.string_indexer import StringIndexer

    vals = np.array(["a", "b", np.nan, "a", np.nan, np.nan], dtype=object)
    frame = Frame({"label": vals})
    model = StringIndexer(handleInvalid="error").fit(frame)
    assert "nan" in model.labels
    out = model.transform(frame)["labelIndex"]
    assert out[2] == out[4] == float(model.labels.index("nan"))


def test_lr_inf_bounds_with_constant_feature():
    rng = np.random.default_rng(11)
    X = rng.normal(size=(500, 3)).astype(np.float32)
    X[:, 1] = 7.0  # zero-variance feature
    y = (X[:, 0] > 0).astype(np.float64)
    frame = Frame({"features": X, "label": y})
    lb = np.array([[-np.inf, -np.inf, 0.0]])
    model = LogisticRegression(
        maxIter=50, lowerBoundsOnCoefficients=lb
    ).fit(frame)
    assert model.coefficients[1] == 0.0  # constant feature -> 0
    assert model.coefficients[2] >= -1e-6


def test_gbt_binary_guard_sees_validation_rows():
    from sntc_tpu.models.tree.gbt import GBTClassifier

    X = np.random.default_rng(0).normal(size=(100, 3)).astype(np.float32)
    y = np.zeros(100)
    y[:10] = 2.0  # multiclass labels hidden in the validation split
    is_val = np.zeros(100, bool)
    is_val[:10] = True
    y[10:60] = 1.0
    frame = Frame({"features": X, "label": y, "isVal": is_val})
    gbt = GBTClassifier(maxIter=3, validationIndicatorCol="isVal")
    with pytest.raises(ValueError, match="binary-only"):
        gbt.fit(frame)


def test_string_indexer_none_roundtrips_through_fit_vocab():
    from sntc_tpu.feature.string_indexer import StringIndexer

    vals = np.array(["a", None, "a", np.nan], dtype=object)
    frame = Frame({"label": vals})
    model = StringIndexer(handleInvalid="error").fit(frame)
    out = model.transform(frame)["labelIndex"]
    assert out[1] == float(model.labels.index("None"))
    assert out[3] == float(model.labels.index("nan"))
