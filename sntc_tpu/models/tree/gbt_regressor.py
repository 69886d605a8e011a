"""GBTRegressor — gradient-boosted regression trees.

Behavioral spec: upstream ``ml/regression/GBTRegressor.scala`` →
``tree/impl/GradientBoostedTrees`` [U]: the FIRST tree fits the raw
labels with weight 1.0 for both losses (we fit the raw residuals of the
constant mean init, which is equivalent); each later round fits a
variance-impurity tree to the loss's negative gradient — squared loss:
``r = y − F`` (leaf = mean residual); absolute loss: ``r = sign(y − F)``
with mean-of-sign leaves, exactly Spark's treatment — then
``F += stepSize · tree(x)``.  ``validationIndicatorCol``
/ ``validationTol`` stop boosting on a validation plateau
(``runWithValidation`` semantics, as in the classifier).

TPU design: the shared dense-heap grower (variance stats) per round,
boosted predictions updated ON DEVICE, serving is one traversal +
tree-weighted contraction — the classifier's machinery with the loss
swapped and no sigmoid.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from sntc_tpu.core.base import Estimator, Model
from sntc_tpu.models.base import CheckpointParams
from sntc_tpu.core.frame import Frame
from sntc_tpu.core.params import Param, validators
from sntc_tpu.models.tree.grower import (
    Forest,
    ForestDeviceMixin,
    ForestPersistenceMixin,
    forest_leaf_stats,
    grow_forest,
    resolve_feature_subset_k,
)
from sntc_tpu.models.tree.gbt import (
    _ValidationTracker,
    _count_round,
    _leaf_values,
    _variance_stats,
)
from sntc_tpu.models.tree.random_forest import _TreeEnsembleParams
from sntc_tpu.obs import module_of, span
from sntc_tpu.ops.binning import bin_features, quantile_bin_edges
from sntc_tpu.parallel.collectives import shard_batch, shard_weights
from sntc_tpu.parallel.context import get_default_mesh

_MODULE = module_of(__name__)


@jax.jit
def _sq_residual_stats(ys, ws, pred):
    """``[1, 3, N]``: the round's one tree's stats in the grower's
    per-tree layout (rows along lanes, as the classifier's)."""
    return _variance_stats(ws, ys - pred)[None]


@jax.jit
def _abs_residual_stats(ys, ws, pred):
    return _variance_stats(ws, jnp.sign(ys - pred))[None]


@partial(jax.jit, static_argnames=("max_depth",))
def _gbt_reg_predict(X, feature, threshold, leaf_stats, tree_weights, *,
                     max_depth):
    """F(x) = Σ_m w_m · tree_m(x): one traversal of all M trees + a
    weighted contraction (one dispatch on the serve path)."""
    means = forest_leaf_stats(
        X, feature, threshold, leaf_stats, max_depth=max_depth, value=True
    )  # [M, N]
    return jnp.einsum("m,mn->n", tree_weights, means)


class _GbtRegParams(_TreeEnsembleParams):
    featuresCol = Param("feature vector column", default="features")
    labelCol = Param("target column", default="label")
    predictionCol = Param("output prediction column", default="prediction")
    maxIter = Param("boosting rounds (trees)", default=20, validator=validators.gt(0))
    stepSize = Param("shrinkage", default=0.1, validator=validators.in_range(0, 1))
    lossType = Param(
        "squared | absolute", default="squared",
        validator=validators.one_of("squared", "absolute"),
    )
    featureSubsetStrategy = Param("feature subset per node", default="all")
    validationIndicatorCol = Param(
        "boolean column marking validation rows; when set, boosting stops "
        "early on validation-loss plateau (Spark runWithValidation)",
        default=None,
    )
    validationTol = Param(
        "relative validation-improvement threshold", default=0.01,
        validator=validators.gteq(0),
    )


class GBTRegressor(_GbtRegParams, CheckpointParams, Estimator):
    def __init__(self, mesh=None, **kwargs):
        super().__init__(**kwargs)
        self._mesh = mesh

    def _fit(self, frame: Frame) -> "GBTRegressionModel":
        mesh = self._mesh or get_default_mesh()
        X = frame[self.getFeaturesCol()]
        if X.ndim != 2:
            raise ValueError(
                f"featuresCol {self.getFeaturesCol()!r} must be a vector "
                "column (use VectorAssembler)"
            )
        X = X.astype(np.float32, copy=False)
        y_all = np.asarray(frame[self.getLabelCol()], np.float32)
        val_col = self.getValidationIndicatorCol()
        if val_col:
            val_mask = np.asarray(frame[val_col]).astype(bool)
            if not val_mask.any() or val_mask.all():
                raise ValueError(
                    "validationIndicatorCol must mark a non-empty proper "
                    "subset of rows"
                )
            X_train, y = X[~val_mask], y_all[~val_mask]
            X_val, y_val = X[val_mask], y_all[val_mask]
        else:
            X_train, y = X, y_all
        n, F = X_train.shape
        n_bins = self.getMaxBins()
        n_rounds = int(self.getMaxIter())
        step = float(self.getStepSize())
        loss = self.getLossType()
        seed = self.getSeed()
        rate = self.getSubsamplingRate()

        edges = quantile_bin_edges(X_train, max_bins=n_bins, seed=seed)
        xs, ys, _ = shard_batch(mesh, X_train, y)
        ws = shard_weights(mesh, np.ones(n, np.float32), xs.shape[0])
        binned = bin_features(xs, jnp.asarray(edges))
        axis = mesh.axis_names[0]
        subset_k = resolve_feature_subset_k(
            self.getFeatureSubsetStrategy(), F, 1, is_classification=False
        )
        grow_kwargs = dict(
            n_bins=n_bins, max_depth=self.getMaxDepth(),
            min_instances_per_node=float(self.getMinInstancesPerNode()),
            min_info_gain=float(self.getMinInfoGain()),
            subset_k=subset_k, impurity="variance",
        )

        def round_weights(i):
            if rate < 1.0:
                r = np.random.default_rng(seed + 7919 * (i + 1))
                mask = (r.random(xs.shape[0]) < rate).astype(np.float32)
            else:
                mask = np.ones(xs.shape[0], np.float32)
            return jax.device_put(
                mask[None, :], NamedSharding(mesh, P(None, axis))
            )

        from sntc_tpu.mlio import optimizer_checkpoint as _ckpt

        init = float(np.mean(y)) if n else 0.0
        pred = jnp.full(xs.shape[0], init, jnp.float32)
        tracker = (
            _ValidationTracker(float(self.getValidationTol()))
            if val_col
            else None
        )
        if val_col:
            X_val_j = jnp.asarray(X_val)
            pred_val = np.full(len(y_val), init, np.float64)
        resid_fn = _sq_residual_stats if loss == "squared" else _abs_residual_stats
        features, thresholds, leaves = [], [], []
        gains, counts = [], []
        weights = []

        # mid-fit round checkpointing (SURVEY.md §5.4), mirroring the
        # classifier: resume skips completed boosting rounds
        ckpt_dir = self.getCheckpointDir()
        interval = self.getCheckpointInterval()
        # NOTE: keep this block in lockstep with GBTClassifier._fit's
        # checkpoint machinery (sntc_tpu/models/tree/gbt.py) — same
        # fingerprint keys, same save-before-break ordering.  n_shards
        # matters because the saved device arrays are PADDED to the mesh
        # size: a resume on a different mesh must restart, not splice.
        fingerprint = {
            "algo": "gbt_reg", "boost_v": 2, "maxIter": n_rounds,
            "n_shards": int(mesh.shape[axis]),
            "maxDepth": self.getMaxDepth(), "stepSize": step,
            "seed": seed, "n_rows": n, "maxBins": n_bins, "loss": loss,
            "subsamplingRate": float(rate),
            "minInstancesPerNode": float(self.getMinInstancesPerNode()),
            "minInfoGain": float(self.getMinInfoGain()),
            "featureSubsetStrategy": str(self.getFeatureSubsetStrategy()),
            "validation": bool(val_col),
            "validationTol": float(self.getValidationTol()),
        }
        start_round = 0
        if ckpt_dir and interval > 0:
            saved = _ckpt.load_state(ckpt_dir, fingerprint)
            if saved is not None and int(saved["round"]) > 0:
                start_round = int(saved["round"])
                features = list(saved["feature"])
                thresholds = list(saved["threshold"])
                leaves = list(saved["leaf_stats"])
                gains = list(saved["gain"])
                counts = list(saved["count"])
                weights = [float(v) for v in saved["tree_weights"]]
                pred = jnp.asarray(saved["pred"])
                if val_col:
                    pred_val = np.asarray(saved["val_pred"], np.float64)
                    tracker.best_err = np.asarray(
                        saved["val_best_err"], np.float64
                    ).reshape(1)
                    tracker.best_m = np.asarray(
                        saved["val_best_m"], np.int64
                    ).reshape(1)
                    tracker.done = np.asarray(saved["val_done"], bool).reshape(1)
                    if tracker.done[0]:
                        start_round = n_rounds
        for m in range(start_round, n_rounds):
            # Spark boost() fits the FIRST tree to the raw labels with
            # weight 1.0 for BOTH losses; fitting the raw residuals of the
            # constant init is equivalent (variance splits are
            # shift-invariant, leaf means shift by init).  Sign residuals
            # (absolute loss) apply only from the second tree on.
            with span("gbt.round", round=m, trees=1, module=_MODULE):
                row_stats = (
                    _sq_residual_stats(ys, ws, pred)
                    if m == 0
                    else resid_fn(ys, ws, pred)
                )
                forest = grow_forest(
                    binned, row_stats, round_weights(m), edges,
                    seed=seed + m, mesh=mesh, **grow_kwargs,
                )
                contrib = _leaf_values(xs, forest)[0]
                tree_w = 1.0 if m == 0 else step
                pred = pred + tree_w * contrib
            _count_round("gbt_regressor", 1)
            features.append(forest.feature[0])
            thresholds.append(forest.threshold[0])
            leaves.append(forest.leaf_stats[0])
            gains.append(forest.gain[0])
            counts.append(forest.count[0])
            weights.append(tree_w)
            if val_col:
                contrib_val = np.asarray(
                    _leaf_values(X_val_j, forest)[0], np.float64
                )
                pred_val = pred_val + tree_w * contrib_val
                err = (
                    float(np.mean((y_val - pred_val) ** 2))
                    if loss == "squared"
                    else float(np.mean(np.abs(y_val - pred_val)))
                )
                # the classifier's Spark runWithValidation bookkeeping —
                # one stop rule for both GBTs
                stopped = tracker.update(m, err)
            else:
                stopped = False
            # save BEFORE honoring the stop so a resume sees done=True
            # (the classifier's ordering)
            if ckpt_dir and interval > 0 and (m + 1) % interval == 0:
                state = {
                    "round": np.int64(m + 1),
                    "feature": np.stack(features),
                    "threshold": np.stack(thresholds),
                    "leaf_stats": np.stack(leaves),
                    "gain": np.stack(gains),
                    "count": np.stack(counts),
                    "tree_weights": np.asarray(weights, np.float64),
                    "pred": np.asarray(pred),
                }
                if val_col:
                    state["val_pred"] = pred_val
                    state["val_best_err"] = tracker.best_err
                    state["val_best_m"] = tracker.best_m
                    state["val_done"] = tracker.done
                _ckpt.save_state(ckpt_dir, state, fingerprint)
            if stopped:
                break

        # a COMPLETED fit owns no checkpoint: leftover state would make a
        # later fit with the same dir silently return this model
        if ckpt_dir and interval > 0:
            _ckpt.clear_state(ckpt_dir)
        # validated boosting always trims to the best round, whether the
        # loop broke early or ran to maxIter (Spark keeps bestM trees)
        keep = int(tracker.best_m[0]) if tracker else len(features)
        forest = Forest(
            feature=np.stack(features[:keep]),
            threshold=np.stack(thresholds[:keep]),
            leaf_stats=np.stack(leaves[:keep]),
            max_depth=self.getMaxDepth(),
            gain=np.stack(gains[:keep]),
            count=np.stack(counts[:keep]),
        )
        model = GBTRegressionModel(
            forest=forest,
            init_prediction=init,
            treeWeights=[float(v) for v in weights[:keep]],
            n_features=F,
        )
        model.setParams(
            **{k2: v for k2, v in self.paramValues().items()
               if model.hasParam(k2)}
        )
        return model


class GBTRegressionModel(
    _GbtRegParams, ForestPersistenceMixin, ForestDeviceMixin, Model
):
    _per_tree_normalization = False  # boosted ensembles (Spark)

    def __init__(self, forest: Forest, init_prediction: float = 0.0,
                 treeWeights=(), n_features: int = 0, **kwargs):
        super().__init__(**kwargs)
        self.forest = forest
        self.init_prediction = float(init_prediction)
        self.treeWeights = [float(v) for v in treeWeights]
        self._n_features = int(n_features)

    @property
    def numTrees(self) -> int:
        return self.forest.feature.shape[0]

    def _extra_meta(self):
        return {
            "init_prediction": self.init_prediction,
            "treeWeights": self.treeWeights,
        }

    @classmethod
    def _from_forest(cls, forest, extra):
        return cls(
            forest=forest,
            init_prediction=float(extra.get("init_prediction", 0.0)),
            treeWeights=extra.get("treeWeights", []),
            n_features=int(extra.get("n_features", 0)),
        )

    def _forest_arrays(self):
        f = self.forest
        return (
            f.feature, f.threshold, f.leaf_stats,
            np.asarray(self.treeWeights, np.float32),
        )

    def predict(self, X: np.ndarray) -> np.ndarray:
        feature, threshold, leaf_stats, tw = self._device_forest()
        out = _gbt_reg_predict(
            jnp.asarray(X, jnp.float32), feature, threshold, leaf_stats, tw,
            max_depth=self.forest.max_depth,
        )
        return self.init_prediction + np.asarray(out, np.float64)

    def transform(self, frame: Frame) -> Frame:
        X = frame[self.getFeaturesCol()].astype(np.float32, copy=False)
        return frame.with_column(self.getPredictionCol(), self.predict(X))
