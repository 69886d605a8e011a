"""UnivariateFeatureSelector — score-function feature selection.

Behavioral spec: upstream ``ml/feature/UnivariateFeatureSelector.scala``
[U] (Spark 3.1's successor to ChiSqSelector, same selection surface the
reference's χ² stage uses [B:9]): the score function is chosen by the
(featureType, labelType) pair —

  * categorical/categorical → χ² test,
  * continuous/categorical  → ANOVA F-test (``f_classif``),
  * continuous/continuous   → F-regression (``f_regression``),

with ``selectionMode`` ∈ {numTopFeatures, percentile, fpr, fdr, fwe} and
one numeric ``selectionThreshold`` knob (defaults: 50 / 0.1 / 0.05 /
0.05 / 0.05).

TPU design: every score reduces to per-feature moments computed in ONE
``tree_aggregate`` SPMD pass over the mesh (χ² reuses the binned
contingency kernel; ANOVA needs per-(feature, class) weight/sum/sumsq;
F-regression needs per-feature x/x²/xy moments).  The F statistics and
p-values (scipy ``f.sf``) are host-side on ``[F]``-sized arrays.
"Categorical" features are quantile-binned like ChiSqSelector (this
framework's continuous-flow extension, SURVEY.md §2.2).
"""

from __future__ import annotations

from functools import lru_cache
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from sntc_tpu.core.base import Estimator, Model
from sntc_tpu.core.frame import Frame
from sntc_tpu.core.params import Param, validators
from sntc_tpu.feature.selection import select_features_by_mode, take_columns
from sntc_tpu.parallel.collectives import make_tree_aggregate, shard_batch
from sntc_tpu.parallel.context import get_default_mesh


@lru_cache(maxsize=None)
def _anova_moments_agg(mesh, n_classes):
    """Per-(feature, class) [count, sum, sumsq] in one SPMD pass,
    accumulated about a pilot row (replicated arg): the F statistic is
    shift-invariant, and raw f32 x² sums catastrophically cancel for
    large-mean features."""

    def moments(xs, ys, w, pilot):
        xs = xs - pilot[None, :]
        oh = jax.nn.one_hot(ys, n_classes, dtype=jnp.float32) * w[:, None]
        cnt = oh.sum(axis=0)  # weighted per-class count
        s = jnp.einsum("nf,nc->fc", xs, oh)
        sq = jnp.einsum("nf,nc->fc", xs * xs, oh)
        return cnt, s, sq

    return make_tree_aggregate(moments, mesh, replicated_args=(3,))


@lru_cache(maxsize=None)
def _regression_moments_agg(mesh):
    """Per-feature [Σw, Σx, Σx², Σy, Σy², Σxy] in one SPMD pass, about
    per-variable pilots (the correlation is shift-invariant; raw f32
    squares cancel for large means)."""

    def moments(xs, ys, w, pilot_x, pilot_y):
        xs = xs - pilot_x[None, :]
        ys = ys - pilot_y
        wx = xs * w[:, None]
        return (
            w.sum(),
            wx.sum(axis=0),
            (xs * wx).sum(axis=0),
            (ys * w).sum(),
            (ys * ys * w).sum(),
            (ys[:, None] * wx).sum(axis=0),
        )

    return make_tree_aggregate(moments, mesh, replicated_args=(3, 4))


def f_classif(X_moments, eps: float = 1e-12):
    """ANOVA F per feature from per-class moments ``(cnt [C], s [F,C],
    sq [F,C])`` — the sklearn ``f_classif`` statistic."""
    from scipy.stats import f as f_dist

    cnt, s, sq = (np.asarray(a, np.float64) for a in X_moments)
    nz = cnt > 0
    k = int(nz.sum())
    n = float(cnt.sum())
    if k < 2 or n <= k:
        F = np.zeros(s.shape[0])
        return F, np.ones_like(F)
    mean_c = s[:, nz] / cnt[nz]
    grand = s.sum(axis=1) / n
    ss_between = (cnt[nz] * (mean_c - grand[:, None]) ** 2).sum(axis=1)
    ss_within = (sq[:, nz] - cnt[nz] * mean_c**2).sum(axis=1)
    F = (ss_between / (k - 1)) / np.maximum(ss_within / (n - k), eps)
    p = f_dist.sf(F, k - 1, n - k)
    return F, p


def f_regression(moments, eps: float = 1e-12):
    """F statistic of the univariate linear fit per feature from
    ``(n, sx, sxx, sy, syy, sxy)`` — the sklearn ``f_regression`` form."""
    from scipy.stats import f as f_dist

    n, sx, sxx, sy, syy, sxy = (np.asarray(a, np.float64) for a in moments)
    n = float(n)
    if n <= 2:
        F = np.zeros(sx.shape[0])
        return F, np.ones_like(F)
    cov = sxy - sx * sy / n
    var_x = sxx - sx**2 / n
    var_y = syy - sy**2 / n
    r2 = cov**2 / np.maximum(var_x * var_y, eps)
    r2 = np.clip(r2, 0.0, 1.0 - eps)
    F = r2 / (1.0 - r2) * (n - 2)
    p = f_dist.sf(F, 1, n - 2)
    return F, p


class _UfsParams:
    featuresCol = Param("input vector column", default="features")
    outputCol = Param("output vector column", default="selectedFeatures")
    labelCol = Param("label column", default="label")
    featureType = Param(
        "categorical | continuous",
        default=None,
        validator=lambda v: v in (None, "categorical", "continuous"),
    )
    labelType = Param(
        "categorical | continuous",
        default=None,
        validator=lambda v: v in (None, "categorical", "continuous"),
    )
    selectionMode = Param(
        "numTopFeatures | percentile | fpr | fdr | fwe",
        default="numTopFeatures",
        validator=validators.one_of(
            "numTopFeatures", "percentile", "fpr", "fdr", "fwe"
        ),
    )
    selectionThreshold = Param(
        "k for numTopFeatures, fraction for percentile, p-cutoff otherwise "
        "(None -> Spark's per-mode default)",
        default=None,
    )
    maxBins = Param(
        "quantile bins when categorical features must be derived from "
        "continuous flows (rebuild-specific)",
        default=32,
        validator=validators.gt(1),
    )


_MODE_DEFAULTS = {
    "numTopFeatures": 50,
    "percentile": 0.1,
    "fpr": 0.05,
    "fdr": 0.05,
    "fwe": 0.05,
}


class UnivariateFeatureSelector(_UfsParams, Estimator):
    def __init__(self, mesh=None, **kwargs):
        super().__init__(**kwargs)
        self._mesh = mesh

    def _score(self, X, y, mesh):
        if X.shape[0] == 0:
            raise ValueError(
                "UnivariateFeatureSelector requires a non-empty dataset"
            )
        ftype, ltype = self.getFeatureType(), self.getLabelType()
        if ftype is None or ltype is None:
            raise ValueError(
                "featureType and labelType must both be set (Spark "
                "requires them; they choose the score function)"
            )
        if ftype == "categorical" and ltype == "categorical":
            # χ² on the binned contingency — ChiSqSelector's one pipeline
            from sntc_tpu.feature.chisq_selector import chi2_scores

            return chi2_scores(X, y, mesh, self.getMaxBins())
        if ltype == "categorical":  # continuous features, ANOVA F
            n_classes = int(y.max()) + 1 if len(y) else 1
            xs, ys, w = shard_batch(mesh, X, y.astype(np.int32))
            pilot = jnp.asarray(np.asarray(X[0], np.float32))
            m = _anova_moments_agg(mesh, n_classes)(xs, ys, w, pilot)
            return f_classif(m)
        if ftype == "categorical":
            raise ValueError(
                "categorical features with a continuous label have no "
                "Spark score function (Spark rejects this combination too)"
            )
        y32 = y.astype(np.float32)
        xs, ys, w = shard_batch(mesh, X, y32)
        m = _regression_moments_agg(mesh)(
            xs, ys, w,
            jnp.asarray(np.asarray(X[0], np.float32)),
            jnp.float32(y32[0]),
        )
        return f_regression(m)

    def _resolved_threshold(self):
        """The mode's threshold, validated BEFORE any distributed scoring
        (threshold semantics depend on the mode, so validation can't live
        in a mode-blind Param validator)."""
        mode = self.getSelectionMode()
        threshold = self.getSelectionThreshold()
        if threshold is None:
            threshold = _MODE_DEFAULTS[mode]
        if mode == "numTopFeatures":
            if float(threshold) != int(threshold):
                raise ValueError(
                    f"selectionThreshold={threshold!r} must be an integer "
                    "feature count for numTopFeatures (Spark IntParam)"
                )
            if int(threshold) < 1:
                raise ValueError(
                    f"selectionThreshold={threshold!r} must be a positive "
                    "feature count for numTopFeatures"
                )
        elif not 0.0 <= float(threshold) <= 1.0:
            raise ValueError(
                f"selectionThreshold={threshold!r} must be in [0, 1] for "
                f"selectionMode={mode!r}"
            )
        return mode, threshold

    def _fit(self, frame: Frame) -> "UnivariateFeatureSelectorModel":
        mesh = self._mesh or get_default_mesh()
        mode, threshold = self._resolved_threshold()  # fail fast
        X = frame[self.getFeaturesCol()].astype(np.float32, copy=False)
        y = np.asarray(frame[self.getLabelCol()])
        stats, p_values = self._score(X, y, mesh)
        selected = select_features_by_mode(
            np.asarray(stats), np.asarray(p_values), mode, threshold,
            X.shape[1],
        )
        model = UnivariateFeatureSelectorModel(selected_features=selected)
        model.setParams(**self.paramValues())
        return model


class UnivariateFeatureSelectorModel(_UfsParams, Model):
    def __init__(self, selected_features: List[int] = (), **kwargs):
        super().__init__(**kwargs)
        self.selected_features = list(selected_features)

    def _save_extra(self):
        return {"selected_features": self.selected_features}, {}

    @classmethod
    def _load_from(cls, params, extra, arrays):
        m = cls(selected_features=extra["selected_features"])
        m.setParams(**params)
        return m

    def transform(self, frame: Frame) -> Frame:
        out = take_columns(
            frame[self.getFeaturesCol()], self.selected_features
        )
        return frame.with_column(self.getOutputCol(), out)
