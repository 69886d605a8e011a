"""RandomForestClassifier — histogram CART forest on TPU [B:9].

Behavioral spec: SURVEY.md §2.3/§3.2 (upstream
``ml/classification/RandomForestClassifier.scala`` + ``tree/impl`` [U]):
quantile binning (``maxBins``), Poisson(subsamplingRate) bootstrap bagging,
level-wise growth with all trees per pass, gini/entropy impurity,
``featureSubsetStrategy`` per node, ``predictRaw`` = sum over trees of the
leaf's class-count vector normalized per tree, probability = normalized raw.

TPU design: sntc_tpu/models/tree/grower.py (dense heaps, segment-sum
histograms, psum across shards).  Differences from Spark, documented:
bagging without replacement uses Bernoulli(subsamplingRate) row masks
(Spark samples exactly); ``minInstancesPerNode`` compares weighted counts.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from sntc_tpu.core.frame import Frame
from sntc_tpu.core.params import Param, validators
from sntc_tpu.models.base import ClassificationModel, ClassifierEstimator
from sntc_tpu.models.tree.grower import (
    Forest,
    ForestDeviceMixin,
    ForestPersistenceMixin,
    forest_leaf_stats,
    grow_forest,
    make_bagging_weights,
    resolve_feature_subset_k,
)
from sntc_tpu.obs import module_of, span
from sntc_tpu.ops.binning import bin_features, quantile_bin_edges
from sntc_tpu.parallel.collectives import shard_batch, shard_weights
from sntc_tpu.parallel.context import get_default_mesh

_MODULE = module_of(__name__)


@partial(jax.jit, static_argnames=("k",))
def _one_hot_stats(ys, ws, k):
    return jax.nn.one_hot(ys, k, dtype=jnp.float32) * ws[:, None]


class _TreeEnsembleParams:
    maxDepth = Param("max tree depth", default=5, validator=validators.in_range(0, 15))
    maxBins = Param("max feature bins", default=32, validator=validators.in_range(2, 256))
    minInstancesPerNode = Param(
        "min (weighted) rows per child", default=1, validator=validators.gteq(1)
    )
    minInfoGain = Param("min split gain", default=0.0, validator=validators.gteq(0))
    subsamplingRate = Param(
        "row sampling rate per tree", default=1.0, validator=validators.in_range(0, 1)
    )
    seed = Param("sampling seed", default=0)


class _RfParams(_TreeEnsembleParams):
    numTrees = Param("number of trees", default=20, validator=validators.gt(0))
    impurity = Param(
        "gini | entropy", default="gini", validator=validators.one_of("gini", "entropy")
    )
    featureSubsetStrategy = Param(
        "auto | all | sqrt | log2 | onethird | int | fraction string",
        default="auto",
    )
    bootstrap = Param("Poisson bootstrap bagging", default=True,
                      validator=validators.is_bool())


class RandomForestClassifier(_RfParams, ClassifierEstimator):
    def __init__(self, mesh=None, **kwargs):
        super().__init__(**kwargs)
        self._mesh = mesh

    def _fit(self, frame: Frame) -> "RandomForestClassificationModel":
        mesh = self._mesh or get_default_mesh()
        with span("rf.extract", module=_MODULE):
            X, y, w = self._extract(frame)
            n, F = X.shape
            k = int(y.max()) + 1 if n else 2
            k = max(k, 2)
        T = self.getNumTrees()
        n_bins = self.getMaxBins()

        with span("rf.bin_edges", module=_MODULE):
            edges = quantile_bin_edges(
                X, max_bins=n_bins, seed=self.getSeed()
            )
        xs, ys, _ = shard_batch(mesh, X, y.astype(np.int32))
        ws = shard_weights(mesh, w, xs.shape[0])
        axis = mesh.axis_names[0]

        binned = bin_features(xs, jnp.asarray(edges))
        row_stats = _one_hot_stats(ys, ws, k)

        w_trees = make_bagging_weights(
            np.random.default_rng(self.getSeed()), self.getBootstrap(),
            self.getSubsamplingRate(), T, xs.shape[0], mesh,
        )

        subset_k = resolve_feature_subset_k(
            self.getFeatureSubsetStrategy(), F, T, is_classification=True
        )
        forest = grow_forest(
            binned, row_stats, w_trees, edges,
            n_bins=n_bins,
            max_depth=self.getMaxDepth(),
            min_instances_per_node=float(self.getMinInstancesPerNode()),
            min_info_gain=float(self.getMinInfoGain()),
            subset_k=subset_k,
            impurity=self.getImpurity(),
            seed=self.getSeed(),
            mesh=mesh,
            row_label=ys, row_weight=ws,  # label-fused scatter path
        )
        model = RandomForestClassificationModel(
            forest=forest, n_classes=k, n_features=F
        )
        model.setParams(
            **{k2: v for k2, v in self.paramValues().items() if model.hasParam(k2)}
        )
        # Spark 3.1+ RandomForestClassificationTrainingSummary: per-class
        # metrics over the training predictions (objectiveHistory is
        # empty — forests have no optimization trace), lazy; binary fits
        # get the threshold-curve variant, as upstream
        from sntc_tpu.models.summary import (
            BinaryClassificationTrainingSummary,
            ClassificationTrainingSummary,
        )

        summary_cls = (
            BinaryClassificationTrainingSummary
            if k == 2
            else ClassificationTrainingSummary
        )
        model.summary = summary_cls(
            [], 0, model, frame, labelCol=self.getLabelCol(), mesh=mesh
        )
        return model


@partial(jax.jit, static_argnames=("max_depth", "traversal"))
def _rf_raw(X, feature, threshold, leaf_stats, *, max_depth,
            traversal="xla"):
    from sntc_tpu.kernels.forest import traverse_forest

    stats = traverse_forest(
        X, feature, threshold, leaf_stats, max_depth=max_depth,
        traversal=traversal,
    )  # [T, N, C]
    totals = stats.sum(axis=2, keepdims=True)
    probs = stats / jnp.maximum(totals, 1e-12)
    return probs.sum(axis=0)  # [N, C] — Spark's summed per-tree votes


@partial(jax.jit, static_argnames=("max_depth", "mode", "traversal"))
def _rf_serve(X, feature, threshold, leaf_stats, thr, *, max_depth, mode,
              traversal="xla"):
    """Traverse + normalize + predict, packed: one dispatch and one
    device→host transfer per serving micro-batch."""
    from sntc_tpu.models.base import pack_serve_outputs

    raw = _rf_raw(
        X, feature, threshold, leaf_stats, max_depth=max_depth,
        traversal=traversal,
    )
    prob = raw / jnp.maximum(raw.sum(axis=1, keepdims=True), 1e-12)
    return pack_serve_outputs(raw, prob, thr, mode)


class RandomForestClassificationModel(
    _RfParams, ForestPersistenceMixin, ForestDeviceMixin, ClassificationModel
):
    def __init__(self, forest: Forest, n_classes: int, n_features: int = 0,
                 **kwargs):
        super().__init__(**kwargs)
        self.forest = forest
        self._n_classes = int(n_classes)
        self._n_features = int(n_features)

    @property
    def num_classes(self) -> int:
        return self._n_classes

    @property
    def trees(self) -> Forest:
        return self.forest

    def _extra_meta(self):
        return {"n_classes": self._n_classes}

    @classmethod
    def _from_forest(cls, forest, extra):
        return cls(
            forest=forest,
            n_classes=int(extra["n_classes"]),
            n_features=int(extra.get("n_features", 0)),
        )

    def _raw_predict(self, X: np.ndarray) -> np.ndarray:
        return np.asarray(
            _rf_raw(
                jnp.asarray(X),
                *self._device_forest(),
                max_depth=self.forest.max_depth,
            )
        )

    def _raw_to_probability(self, raw: np.ndarray) -> np.ndarray:
        totals = raw.sum(axis=1, keepdims=True)
        return raw / np.maximum(totals, 1e-12)

    def _predict_all_dev(self, X: np.ndarray):
        from sntc_tpu.kernels import serve_kernel_call

        mode, thr = self._threshold_mode()
        Xd = jnp.asarray(X)
        fa, ta, ls = self._device_forest()
        md = self.forest.max_depth

        def run(traversal):
            return _rf_serve(
                Xd, fa, ta, ls, jnp.asarray(thr),
                max_depth=md, mode=mode, traversal=traversal,
            )

        return serve_kernel_call(
            "forest_traversal", (Xd, fa, ta, ls), run,
            lambda: run("xla"), static=(md, mode),
            guard_kwargs={
                "n_nodes": fa.shape[1], "n_features": Xd.shape[1],
                "n_stats": ls.shape[2], "itemsize": Xd.dtype.itemsize,
            },
        )
