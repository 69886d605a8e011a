"""ChiSqSelector — χ² flow-feature selection [B:9].

Behavioral spec: SURVEY.md §2.2 (upstream ``ml/feature/ChiSqSelector.scala``
-> ``mllib/stat/test/ChiSqTest.scala`` [U]): rank features by χ² p-value
against the label (ascending, i.e. most significant first) and keep the top
``numTopFeatures`` / ``percentile`` / all below ``fpr``.  Spark's χ² needs
categorical features; continuous flow features are quantile-binned first
(SURVEY.md §2.2 rebuild note).

TPU design: binning + the (feature, bin, class) contingency run on-device —
``bin_features`` + ``binned_contingency`` fused in one ``tree_aggregate``
SPMD pass over the mesh; the χ² statistics and selection happen on host
(78×32×15 — trivial).  The same histogram kernel drives the tree growers.
"""

from __future__ import annotations

from functools import lru_cache
from typing import List

import jax.numpy as jnp
import numpy as np

from sntc_tpu.core.base import Estimator, Model
from sntc_tpu.core.frame import Frame
from sntc_tpu.core.params import Param, validators
from sntc_tpu.feature.selection import select_features_by_mode, take_columns
from sntc_tpu.obs import inc, module_of, span
from sntc_tpu.ops.binning import bin_features, quantile_bin_edges
from sntc_tpu.ops.histogram import (
    binned_contingency,
    binned_contingency_onehot,
    chi_square,
)
from sntc_tpu.parallel.collectives import make_tree_aggregate, shard_batch
from sntc_tpu.parallel.context import get_default_mesh

_MODULE = module_of(__name__)


@lru_cache(maxsize=None)
def _contingency_agg(mesh, n_bins, n_classes, impl):
    """One compiled contingency program per configuration across fits
    (edges arrive as a replicated ARGUMENT, not a baked-in constant —
    rebuilding the aggregate per fit recompiled on every call)."""

    def contingency(xs, ys, w, edges):
        binned = bin_features(xs, edges)
        if impl == "pallas":
            return binned_contingency_onehot(
                binned, ys, w, n_bins=n_bins, n_classes=n_classes
            )
        return binned_contingency(
            binned, ys, w, n_bins=n_bins, n_classes=n_classes
        )

    return make_tree_aggregate(
        contingency, mesh,
        check_vma=impl != "pallas",
        replicated_args=(3,),
    )


class _SelectorParams:
    featuresCol = Param("input vector column", default="features")
    outputCol = Param("output vector column", default="selectedFeatures")
    labelCol = Param("label index column", default="label")
    selectorType = Param(
        "selection mode: numTopFeatures | percentile | fpr | fdr | fwe",
        default="numTopFeatures",
        validator=validators.one_of(
            "numTopFeatures", "percentile", "fpr", "fdr", "fwe"
        ),
    )
    numTopFeatures = Param(
        "number of features to keep", default=50, validator=validators.gt(0)
    )
    percentile = Param(
        "fraction of features to keep", default=0.1, validator=validators.in_range(0, 1)
    )
    fpr = Param(
        "highest p-value to keep", default=0.05, validator=validators.in_range(0, 1)
    )
    fdr = Param(
        "upper bound on the expected false-discovery rate "
        "(Benjamini-Hochberg)",
        default=0.05,
        validator=validators.in_range(0, 1),
    )
    fwe = Param(
        "upper bound on the family-wise error rate: keep p < fwe / F "
        "(Bonferroni)",
        default=0.05,
        validator=validators.in_range(0, 1),
    )
    maxBins = Param(
        "quantile bins for continuous features (rebuild-specific; Spark "
        "requires pre-categorical input)",
        default=32,
        validator=validators.gt(1),
    )


def chi2_scores(X: np.ndarray, y: np.ndarray, mesh, n_bins: int):
    """``(stats [F], p_values [F])`` of the binned χ² test — the one chi2
    scoring pipeline shared by ChiSqSelector and
    UnivariateFeatureSelector's categorical/categorical mode."""
    from sntc_tpu.ops.pallas_histogram import tree_hist_impl

    y = np.asarray(y).astype(np.int32)
    n_classes = int(y.max()) + 1 if len(y) else 1
    with span("chi2.bin_edges", module=_MODULE):
        edges = quantile_bin_edges(X, max_bins=n_bins)
    xs, ys, w = shard_batch(mesh, X, y)
    impl = tree_hist_impl(1, n_bins, mesh)
    observed = _contingency_agg(mesh, n_bins, n_classes, impl)(
        xs, ys, w, jnp.asarray(edges)
    )
    with span("d2h.fetch", what="contingency", module=_MODULE):
        observed = np.asarray(observed)
    stats, p_values, _ = chi_square(observed)
    return stats, p_values


class ChiSqSelector(_SelectorParams, Estimator):
    def __init__(self, mesh=None, **kwargs):
        super().__init__(**kwargs)
        self._mesh = mesh

    def _fit(self, frame: Frame) -> "ChiSqSelectorModel":
        mesh = self._mesh or get_default_mesh()
        with span("chi2.extract", module=_MODULE):
            col = frame[self.getFeaturesCol()]
            # the frame's own matrix when it is float32 already (the
            # assembler's is): the upload then keys the device cache on
            # an array that outlives this stage, as a re-fit wants
            X = col.astype(np.float32, copy=False)
            if X is not col:
                inc("sntc_feature_copy_bytes_total", X.nbytes,
                    site="chi2.extract")
            y = frame[self.getLabelCol()]
        stats, p_values = chi2_scores(X, y, mesh, self.getMaxBins())

        mode = self.getSelectorType()
        threshold = {
            "numTopFeatures": self.getNumTopFeatures(),
            "percentile": self.getPercentile(),
            "fpr": self.getFpr(),
            "fdr": self.getFdr(),
            "fwe": self.getFwe(),
        }[mode]
        selected = select_features_by_mode(
            stats, p_values, mode, threshold, X.shape[1]
        )

        model = ChiSqSelectorModel(selected_features=selected)
        model.setParams(**self.paramValues())
        return model


class ChiSqSelectorModel(_SelectorParams, Model):
    def __init__(self, selected_features: List[int], **kwargs):
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
