"""``sntc_tpu.stat`` — the ``pyspark.ml.stat`` surface, TPU-first.

Behavioral spec: Spark's ``ml/stat/{Correlation,ChiSquareTest,ANOVATest,
FValueTest,KolmogorovSmirnovTest,Summarizer}.scala`` [U] (the hypothesis-test
statistics themselves live in ``mllib/stat/test/*`` [U]; SURVEY.md §2.2 maps
the χ² machinery).  Spark returns each result as a one-row DataFrame of
vector/matrix structs; here the same values come back as a one-row
:class:`~sntc_tpu.core.frame.Frame` whose 2-D columns are the vectors (and,
for ``Correlation``, an ``[F, F]`` frame of matrix rows) — the eager analog
of Spark's lazy result row.

TPU design: every O(N) reduction is ONE fused SPMD pass over the
mesh-sharded rows (``make_tree_aggregate`` → per-shard partials → ``psum``):

* ``Correlation`` (pearson): the Gram matrix ``Xᶜᵀ Xᶜ`` is a single [F,N]×
  [N,F] contraction per shard — pure MXU work; spearman is the same pass on
  average-tie ranks (rank transform on host: a global sort is host work,
  exactly Spark's ``zipWithIndex`` rank stage).
* ``Summarizer``: count/weightSum/mean/variance/L1/L2/nnz/min/max in one
  program.  min/max ride the sum-only ``psum`` via a one-hot-by-
  ``axis_index`` outer product (each shard deposits its row extrema in its
  own row of a ``[n_dev, F]`` partial; the host folds the tiny stack).
  Padding rows replicate a real row (collectives.shard_batch), so raw
  extrema need no masking.
* χ²/ANOVA/F-value reuse the selector aggregates (`feature/chisq_selector`,
  `feature/univariate_selector`) — one statistics engine, two surfaces,
  matching Spark where ``ChiSqSelector`` and ``ChiSquareTest`` share
  ``mllib.stat.Statistics``.
* KS runs host-side end to end (sort + CDF + Kolmogorov p): a 1-D sort
  whose downstream work is all host would only lose float64 precision on
  a device round-trip (x64 is off device-side; commons-math computes in
  double) — the SURVEY.md §2.4 "on host" exception class.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from sntc_tpu.core.frame import Frame
from sntc_tpu.feature.univariate_selector import (
    _anova_moments_agg,
    _regression_moments_agg,
    f_classif,
    f_regression,
)
from sntc_tpu.ops.histogram import (
    binned_contingency,
    binned_contingency_onehot,
    chi_square,
)
from sntc_tpu.ops.pallas_histogram import tree_hist_impl
from sntc_tpu.parallel.collectives import (
    make_tree_aggregate,
    shard_batch,
    shard_weights,
)
from sntc_tpu.parallel.context import get_default_mesh
from sntc_tpu.parallel.mesh import DATA_AXIS

__all__ = [
    "ANOVATest",
    "ChiSquareTest",
    "Correlation",
    "FValueTest",
    "KolmogorovSmirnovTest",
    "Summarizer",
]


def _features_matrix(frame: Frame, col: str) -> np.ndarray:
    X = frame[col]
    if X.ndim == 1:
        X = np.asarray(X)[:, None]
    return X


# ---------------------------------------------------------------------------
# Correlation
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _corr_moments_agg(mesh):
    """``(Σw, Σw·xᶜ [F], xᶜᵀ diag(w) xᶜ [F,F])`` about a replicated pilot
    row — the Gram contraction is the MXU op; the pilot shift keeps f32
    squares from cancelling (same idiom as the selector aggregates)."""

    def moments(xs, w, pilot):
        xc = xs - pilot[None, :]
        wx = xc * w[:, None]
        return w.sum(), wx.sum(axis=0), xc.T @ wx

    return make_tree_aggregate(moments, mesh, replicated_args=(2,))


def _rank_columns(X: np.ndarray) -> np.ndarray:
    """Average-tie ranks per column (Spark's Spearman rank stage [U]:
    ties share the mean of their positional ranks)."""
    from scipy.stats import rankdata

    return np.stack(
        [rankdata(X[:, j], method="average") for j in range(X.shape[1])],
        axis=1,
    ).astype(np.float32)


class Correlation:
    """``ml.stat.Correlation.corr`` [U]: the F×F correlation matrix of a
    vector column.  Returns an ``[F, F]`` Frame (row ``i`` = matrix row
    ``i``) under the method-name column, the eager analog of Spark's
    one-Matrix-row DataFrame."""

    @staticmethod
    def corr(
        frame: Frame,
        column: str,
        method: str = "pearson",
        mesh=None,
    ) -> Frame:
        if method not in ("pearson", "spearman"):
            raise ValueError(
                f"method must be 'pearson' or 'spearman', got {method!r}"
            )
        mesh = mesh or get_default_mesh()
        X = _features_matrix(frame, column).astype(np.float32)
        if X.shape[0] < 1:
            raise ValueError("Correlation requires a non-empty dataset")
        if method == "spearman":
            X = _rank_columns(X)
        xs, w = shard_batch(mesh, X)
        n, s, gram = _corr_moments_agg(mesh)(xs, w, jnp.asarray(X[0]))
        n = float(n)
        s = np.asarray(s, np.float64)
        cov = np.asarray(gram, np.float64) - np.outer(s, s) / n
        d = np.sqrt(np.maximum(np.diag(cov), 0.0))
        with np.errstate(divide="ignore", invalid="ignore"):
            m = cov / np.outer(d, d)
        # Spark yields NaN for zero-variance features; the diagonal is 1
        m[np.isinf(m)] = np.nan
        np.fill_diagonal(m, 1.0)
        return Frame({method: np.clip(m, -1.0, 1.0)})


# ---------------------------------------------------------------------------
# Hypothesis tests
# ---------------------------------------------------------------------------

def _test_frame(stats, pvals, dofs, flatten: bool) -> Frame:
    stats = np.asarray(stats, np.float64)
    pvals = np.asarray(pvals, np.float64)
    dofs = np.asarray(dofs, np.int64)
    if flatten:
        return Frame(
            {
                "featureIndex": np.arange(stats.shape[0], dtype=np.int64),
                "pValue": pvals,
                "degreesOfFreedom": dofs,
                "statistic": stats,
            }
        )
    return Frame(
        {
            "pValues": pvals[None, :],
            "degreesOfFreedom": dofs[None, :],
            "statistics": stats[None, :],
        }
    )


class ChiSquareTest:
    """``ml.stat.ChiSquareTest`` [U]: Pearson χ² independence test of every
    categorical feature against a categorical label.  Feature values are
    factorized on host (Spark's ``distinct`` stage); the (feature, value,
    class) contingency is one SPMD ``segment_sum`` pass on the mesh."""

    #: Spark's ChiSqTest "maxCategories" guard [U]: a feature with more
    #: distinct values than this is almost surely continuous — reject it
    #: rather than build a degenerate table.
    MAX_CATEGORIES = 10_000

    @staticmethod
    def test(
        frame: Frame,
        featuresCol: str,
        labelCol: str,
        flatten: bool = False,
        mesh=None,
    ) -> Frame:
        mesh = mesh or get_default_mesh()
        X = _features_matrix(frame, featuresCol)
        y = np.asarray(frame[labelCol])
        classes, y_idx = np.unique(y, return_inverse=True)
        cols, cards = [], []
        for j in range(X.shape[1]):
            vals, idx = np.unique(X[:, j], return_inverse=True)
            if len(vals) > ChiSquareTest.MAX_CATEGORIES:
                raise ValueError(
                    f"feature {j} has {len(vals)} distinct values "
                    f"(> {ChiSquareTest.MAX_CATEGORIES}); χ² requires "
                    "categorical features — bin or discretize first"
                )
            cols.append(idx)
            cards.append(len(vals))
        binned = np.stack(cols, axis=1).astype(np.int32)
        n_bins = max(cards)
        xs, ys, w = shard_batch(mesh, binned, y_idx.astype(np.int32))
        impl = tree_hist_impl(1, n_bins, mesh)
        agg = _contingency_count_agg(mesh, n_bins, len(classes), impl)
        observed = np.asarray(agg(xs, ys, w))
        stats, pvals, dofs = chi_square(observed)
        return _test_frame(stats, pvals, dofs, flatten)


@lru_cache(maxsize=None)
def _contingency_count_agg(mesh, n_bins, n_classes, impl):
    """Same impl dispatch as ``chisq_selector._contingency_agg``: the
    one-hot MXU kernel on TPU (scatter-adds serialize there),
    ``segment_sum`` elsewhere."""

    def contingency(binned, ys, w):
        if impl == "pallas":
            return binned_contingency_onehot(
                binned, ys, w, n_bins=n_bins, n_classes=n_classes
            )
        return binned_contingency(
            binned, ys, w, n_bins=n_bins, n_classes=n_classes
        )

    return make_tree_aggregate(
        contingency, mesh, check_vma=impl != "pallas"
    )


class ANOVATest:
    """``ml.stat.ANOVATest`` [U] (Spark 3.1): one-way ANOVA F-test of
    continuous features against a categorical label — the
    ``UnivariateFeatureSelector`` continuous/categorical score as a
    standalone test surface."""

    @staticmethod
    def test(
        frame: Frame,
        featuresCol: str,
        labelCol: str,
        flatten: bool = False,
        mesh=None,
    ) -> Frame:
        mesh = mesh or get_default_mesh()
        X = _features_matrix(frame, featuresCol).astype(np.float32)
        y = np.asarray(frame[labelCol]).astype(np.int32)
        if X.shape[0] == 0:
            raise ValueError("ANOVATest requires a non-empty dataset")
        n_classes = int(y.max()) + 1
        xs, ys, w = shard_batch(mesh, X, y)
        cnt, s, sq = _anova_moments_agg(mesh, n_classes)(
            xs, ys, w, jnp.asarray(X[0])
        )
        F, p = f_classif((cnt, s, sq))
        k = int((np.asarray(cnt) > 0).sum())
        n = float(np.asarray(cnt).sum())
        dof = np.full(F.shape[0], max(int(n) - k, 0), dtype=np.int64)
        return _test_frame(F, p, dof, flatten)


class FValueTest:
    """``ml.stat.FValueTest`` [U] (Spark 3.1): univariate linear-fit F-test
    of continuous features against a continuous label."""

    @staticmethod
    def test(
        frame: Frame,
        featuresCol: str,
        labelCol: str,
        flatten: bool = False,
        mesh=None,
    ) -> Frame:
        mesh = mesh or get_default_mesh()
        X = _features_matrix(frame, featuresCol).astype(np.float32)
        y = np.asarray(frame[labelCol]).astype(np.float32)
        if X.shape[0] == 0:
            raise ValueError("FValueTest requires a non-empty dataset")
        xs, ys, w = shard_batch(mesh, X, y)
        m = _regression_moments_agg(mesh)(
            xs, ys, w, jnp.asarray(X[0]), jnp.float32(y[0])
        )
        F, p = f_regression(m)
        n = float(np.asarray(m[0]))
        dof = np.full(F.shape[0], max(int(n) - 2, 0), dtype=np.int64)
        return _test_frame(F, p, dof, flatten)


class KolmogorovSmirnovTest:
    """``ml.stat.KolmogorovSmirnovTest`` [U]: one-sample, two-sided KS test
    of a sample column against a theoretical distribution, host-side in
    float64 (Spark delegates to commons-math ``KolmogorovSmirnovTest``
    [U], which computes in double; the asymptotic Kolmogorov p-value is
    the same form)."""

    @staticmethod
    def test(
        frame: Frame,
        sampleCol: str,
        distName: str = "norm",
        *params: float,
    ) -> Frame:
        from scipy import stats as sps

        if distName != "norm":
            raise ValueError(
                "only distName='norm' is supported (the one distribution "
                "Spark's KolmogorovSmirnovTest ships [U])"
            )
        x = np.asarray(frame[sampleCol]).astype(np.float64).ravel()
        n = x.shape[0]
        if n == 0:
            raise ValueError("KolmogorovSmirnovTest requires a non-empty sample")
        if len(params) not in (0, 2):
            raise ValueError(
                "distName='norm' takes zero params (standard normal) or "
                f"exactly (mean, std); got {len(params)}"
            )
        mean, std = (params if len(params) == 2 else (0.0, 1.0))
        # host sort: keeps the sample in float64 end to end (x64 is off
        # device-side, and commons-math/Spark compute in double); the
        # downstream CDF work is host-side anyway
        x_sorted = np.sort(x)
        cdf = sps.norm.cdf(x_sorted, loc=mean, scale=std)
        i = np.arange(1, n + 1, dtype=np.float64)
        d = float(np.max(np.maximum(cdf - (i - 1) / n, i / n - cdf)))
        p = float(sps.kstwobign.sf(d * np.sqrt(n)))
        return Frame(
            {"pValue": np.array([p]), "statistic": np.array([d])}
        )


# ---------------------------------------------------------------------------
# Summarizer
# ---------------------------------------------------------------------------

_SUMMARY_METRICS = (
    "mean",
    "sum",
    "variance",
    "std",
    "count",
    "numNonZeros",
    "max",
    "min",
    "normL1",
    "normL2",
    "weightSum",
)


@lru_cache(maxsize=None)
def _summary_agg(mesh):
    """Every Summarizer metric from ONE fused pass.  Moment sums are taken
    about a replicated pilot row (f32 cancellation); norms/nnz use the raw
    values (sums of non-negatives — no cancellation).  min/max become
    psum-able by depositing each shard's extrema into its own row of a
    ``[n_dev, F]`` one-hot outer product."""
    n_dev = mesh.shape[DATA_AXIS]

    def moments(xs, wr, pilot):
        xc = xs - pilot[None, :]
        wx = xc * wr[:, None]
        oh = jax.nn.one_hot(
            jax.lax.axis_index(DATA_AXIS), n_dev, dtype=jnp.float32
        )
        # Spark's SummarizerBuffer skips weight-0 instances entirely, so
        # extrema and count consider only wr>0 rows (this also masks the
        # padding rows).  ±FLT_MAX sentinels — not ±inf — keep the one-hot
        # outer product NaN-free when a shard holds no real rows.
        live = wr[:, None] > 0
        big = jnp.float32(np.finfo(np.float32).max)
        mn = oh[:, None] * jnp.where(live, xs, big).min(axis=0)[None, :]
        mx = oh[:, None] * jnp.where(live, xs, -big).max(axis=0)[None, :]
        return {
            "count": (wr > 0).sum().astype(jnp.float32),
            "wsum": wr.sum(),
            "w2sum": (wr * wr).sum(),
            "s1": wx.sum(axis=0),
            "s2": (xc * wx).sum(axis=0),
            "l1": (jnp.abs(xs) * wr[:, None]).sum(axis=0),
            "l2sq": (xs * xs * wr[:, None]).sum(axis=0),
            "nnz": ((xs != 0) * wr[:, None]).sum(axis=0),
            "mn": mn,
            "mx": mx,
        }

    return make_tree_aggregate(moments, mesh, replicated_args=(2,))


class SummaryBuilder:
    """The object ``Summarizer.metrics(...)`` returns [U].  ``summary``
    computes the requested metrics eagerly (our Frames are eager; Spark's
    builder emits a lazy struct column)."""

    def __init__(self, metrics):
        unknown = [m for m in metrics if m not in _SUMMARY_METRICS]
        if unknown:
            raise ValueError(
                f"unknown summary metrics {unknown}; choose from "
                f"{_SUMMARY_METRICS}"
            )
        self._metrics = tuple(metrics)

    def summary(
        self,
        frame: Frame,
        col: str = "features",
        weightCol: Optional[str] = None,
        mesh=None,
        weightNorm: str = "reliability",
    ) -> Frame:
        """``weightNorm`` (extension; Spark has no knob): "reliability"
        (default) matches ``ml.stat`` SummarizerBuffer's unbiased
        denominator Σw − Σw²/Σw; "frequency" uses Σw − 1, under which
        ``weightCol`` ≡ integer row replication (the contract the
        framework's weighted FITS pin).  Unweighted they coincide."""
        mesh = mesh or get_default_mesh()
        X = _features_matrix(frame, col).astype(np.float32)
        if X.shape[0] == 0:
            raise ValueError("Summarizer requires a non-empty dataset")
        xs, mask = shard_batch(mesh, X)
        if weightCol is not None:
            wr = shard_weights(
                mesh,
                np.asarray(frame[weightCol]).astype(np.float32),
                xs.shape[0],
            )
        else:
            wr = mask  # padding rows carry weight 0 either way
        m = _summary_agg(mesh)(xs, wr, jnp.asarray(X[0]))
        m = {k: np.asarray(v, np.float64) for k, v in m.items()}
        wsum, pilot = m["wsum"], X[0].astype(np.float64)
        if wsum <= 0:
            raise ValueError(
                "Summarizer: total weight is zero (all rows weight-0)"
            )
        mean = pilot + m["s1"] / wsum
        # unbiased variance.  Default denominator is the RELIABILITY-
        # weight form Σw − Σw²/Σw — exactly Spark's ml.stat
        # SummarizerBuffer/MultivariateOnlineSummarizer (parity; r5 closed
        # the former frequency-denominator delta).  "frequency" keeps the
        # Σw − 1 replication contract as an opt-in extension.
        if weightNorm not in ("reliability", "frequency"):
            raise ValueError(
                f"weightNorm must be 'reliability' or 'frequency', got "
                f"{weightNorm!r}"
            )
        denom = float(
            wsum - m["w2sum"] / wsum
            if weightNorm == "reliability"
            else wsum - 1.0
        )
        # Spark: a non-positive denominator (single row / one dominant
        # weight) yields zero variance, not a division blow-up
        if denom > 0:
            var = np.maximum(
                (m["s2"] - m["s1"] ** 2 / wsum) / denom, 0.0
            )
        else:
            var = np.zeros_like(mean)
        values = {
            "mean": mean,
            "sum": mean * wsum,
            "variance": var,
            "std": np.sqrt(var),
            "count": np.int64(round(float(m["count"]))),
            "numNonZeros": m["nnz"],
            "max": m["mx"].max(axis=0),
            "min": m["mn"].min(axis=0),
            "normL1": m["l1"],
            "normL2": np.sqrt(m["l2sq"]),
            "weightSum": float(wsum),
        }
        out = {}
        for name in self._metrics:
            v = values[name]
            out[name] = (
                np.asarray(v)[None, :] if np.ndim(v) == 1
                else np.asarray([v])
            )
        return Frame(out)


class Summarizer:
    """``ml.stat.Summarizer`` [U]: vector-column summary statistics in one
    pass.  ``Summarizer.metrics("mean", "variance").summary(df, "features",
    weightCol)`` — the Spark call shape, eager result."""

    @staticmethod
    def metrics(*names: str) -> SummaryBuilder:
        if not names:
            raise ValueError("Summarizer.metrics requires at least one metric")
        return SummaryBuilder(names)

    # Spark's single-metric shorthands [U]
    @staticmethod
    def mean(frame, col="features", weightCol=None, mesh=None):
        return SummaryBuilder(("mean",)).summary(frame, col, weightCol, mesh)

    @staticmethod
    def variance(frame, col="features", weightCol=None, mesh=None):
        return SummaryBuilder(("variance",)).summary(
            frame, col, weightCol, mesh
        )
