"""GBTClassifier — gradient-boosted trees, binary logistic loss [B:10].

Behavioral spec: SURVEY.md §2.3 (upstream
``ml/tree/impl/GradientBoostedTrees.scala`` + ``GBTClassifier`` [U]):
labels map to {-1, +1}; the first tree is a plain regression fit to the
signed labels (weight 1.0); each later round fits a variance-impurity
regression tree to the Friedman pseudo-residuals ``2y / (1 + exp(2·y·F))``
and adds it with ``stepSize`` (default 0.1) shrinkage; **binary only** —
the reference wraps OneVsRest for 15 classes.  ``rawPrediction`` is
``[-2F, 2F]`` and probability the logistic of it, matching Spark's
loss-based probability.

TPU design: reuses the binned grower (variance stats ``[w, wy, wy²]``);
per-round residual updates run on-device from the previous margins — the
dataset never leaves HBM across rounds (SURVEY.md §7.1 step 4).
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
from sntc_tpu.mlio import optimizer_checkpoint as _ckpt
from sntc_tpu.models.base import (
    CheckpointParams,
    ClassificationModel,
    ClassifierEstimator,
)
from sntc_tpu.models.tree.grower import (
    Forest,
    ForestDeviceMixin,
    forest_leaf_stats,
    grow_forest,
    resolve_feature_subset_k,
)
from sntc_tpu.models.tree.random_forest import _TreeEnsembleParams
from sntc_tpu.obs import inc, module_of, span
from sntc_tpu.ops.binning import bin_features, quantile_bin_edges
from sntc_tpu.parallel.collectives import shard_batch, shard_weights
from sntc_tpu.parallel.context import get_default_mesh

_MODULE = module_of(__name__)


def _count_round(estimator: str, trees: int) -> None:
    """One boosting round of ``trees`` trees is done (the regressor and
    both classifier loops count here)."""
    inc("sntc_boost_rounds_total", estimator=estimator)
    inc("sntc_boost_trees_total", trees, estimator=estimator)


def _variance_stats(ws, r):
    """Variance stats ``[w, wr, wr²]`` of residuals ``r``, stacked before
    the row axis: ``[N]`` residuals give ``[3, N]``, a one-vs-rest round's
    ``[K, N]`` give ``[K, 3, N]``, the grower's per-tree layout (rows along
    lanes; a ``[K, N, 3]`` array would lie tiled to 128 lanes on the TPU).
    The one layout rule of the binary, one-vs-rest and regression loops."""
    w = jnp.broadcast_to(ws, r.shape)
    return jnp.stack([w, w * r, w * r * r], axis=-2)


@jax.jit
def _residual_stats(y_signed, ws, margin):
    """Friedman pseudo-residuals for logistic loss -> variance stats."""
    r = 2.0 * y_signed / (1.0 + jnp.exp(2.0 * y_signed * margin))
    return _variance_stats(ws, r)


@jax.jit
def _label_stats(y_signed, ws):
    """Round 0 fits the signed labels themselves."""
    return _variance_stats(ws, y_signed)


def _leaf_values(X, forest):
    """``[T, N]``: every row's leaf mean in each tree of a round's
    ``forest`` (host heaps, a few KB), by the package's one walk."""
    return forest_leaf_stats(
        X, jnp.asarray(forest.feature), jnp.asarray(forest.threshold),
        jnp.asarray(forest.leaf_stats), max_depth=forest.max_depth,
        value=True,
    )


@partial(jax.jit, static_argnames=("num_classes",))
def _ovr_signed_labels(ys, *, num_classes):
    """[K, N] signed one-vs-rest labels: +1 where y==k else -1."""
    k = jnp.arange(num_classes)[:, None]
    return (2.0 * (ys[None, :] == k) - 1.0).astype(jnp.float32)


@partial(jax.jit, static_argnames=("num_classes",))
def _broadcast_classes(v, *, num_classes):
    """``[N]`` -> ``[K, N]``, rows still sharded as ``v`` is.  A module-level
    program: one built inside the fit would be a new ``jit`` object, and so
    a compile-cache miss, every fit."""
    return jnp.broadcast_to(v[None], (num_classes,) + v.shape)


def _prepare_boosting(classifier: "GBTClassifier", X, y, w, mesh):
    """Shared boosting setup for the sequential (binary, checkpointable)
    and vectorized one-vs-rest paths — ONE place for the bin edges,
    sharding, grower kwargs, and the per-round subsample-mask seed, so the
    two paths cannot drift apart (they must train identical trees)."""
    n, F = X.shape
    n_bins = classifier.getMaxBins()
    seed = classifier.getSeed()
    rate = classifier.getSubsamplingRate()

    with span("gbt.bin_edges", module=_MODULE):
        edges = quantile_bin_edges(X, max_bins=n_bins, seed=seed)
    xs, ys, _ = shard_batch(mesh, X, y.astype(np.int32))
    ws = shard_weights(mesh, w, xs.shape[0])
    binned = bin_features(xs, jnp.asarray(edges))

    subset_k = resolve_feature_subset_k(
        classifier.getFeatureSubsetStrategy(), F, 1, is_classification=False
    )
    grow_kwargs = dict(
        n_bins=n_bins,
        max_depth=classifier.getMaxDepth(),
        min_instances_per_node=float(classifier.getMinInstancesPerNode()),
        min_info_gain=float(classifier.getMinInfoGain()),
        subset_k=subset_k,
        impurity="variance",
    )

    def round_mask(i: int) -> np.ndarray:
        """Host [n_pad] subsample mask for boosting round ``i`` —
        per-round seeded: resume-deterministic (checkpointing)."""
        with span("gbt.mask", round=i, module=_MODULE):
            if rate < 1.0:
                r = np.random.default_rng(seed + 7919 * (i + 1))
                return (r.random(xs.shape[0]) < rate).astype(np.float32)
            return np.ones(xs.shape[0], np.float32)

    return edges, xs, ys, ws, binned, grow_kwargs, round_mask


class _GbtParams(_TreeEnsembleParams):
    maxIter = Param("boosting rounds (trees)", default=20, validator=validators.gt(0))
    stepSize = Param("shrinkage", default=0.1, validator=validators.in_range(0, 1))
    lossType = Param(
        "boosting loss", default="logistic", validator=validators.one_of("logistic")
    )
    featureSubsetStrategy = Param("feature subset per node", default="all")
    validationIndicatorCol = Param(
        "boolean column marking validation rows; when set, boosting stops "
        "early on validation-loss plateau (Spark runWithValidation)",
        default=None,
    )
    validationTol = Param(
        "early-stop threshold on validation-loss improvement",
        default=0.01,
        validator=validators.gteq(0),
    )


def _validation_error(margin, y_signed, w):
    """Spark ``LogLoss.computeError``: weighted mean of
    ``2·log1p(exp(-2·y·F))`` over the validation rows."""
    loss = 2.0 * np.logaddexp(
        0.0,
        -2.0 * np.asarray(y_signed, np.float64) * np.asarray(margin, np.float64),
    )
    w = np.asarray(w, np.float64)
    return np.sum(w * loss, axis=-1) / np.sum(w)


class _ValidationTracker:
    """Spark ``GradientBoostedTrees.boost`` validated-stop bookkeeping.

    After round 0 the first error seeds ``best``; for each later round,
    stop when the improvement over ``best`` falls below
    ``tol * max(current, 0.01)``, else record a new best.  The final model
    keeps ``best_m`` trees (the stopping round's tree is discarded).
    ``k > 1`` tracks one-vs-rest classes independently (per-class stop,
    global loop end when all classes are done).
    """

    def __init__(self, tol: float, k: int = 1):
        self.tol = float(tol)
        self.best_err = np.full(k, np.inf)
        self.best_m = np.zeros(k, np.int64)
        self.done = np.zeros(k, bool)

    def update(self, round_idx: int, errs) -> bool:
        errs = np.atleast_1d(np.asarray(errs, np.float64))
        for i, err in enumerate(errs):
            if self.done[i]:
                continue
            if round_idx == 0:
                self.best_err[i] = err
                self.best_m[i] = 1
            elif self.best_err[i] - err < self.tol * max(err, 0.01):
                self.done[i] = True
            elif err < self.best_err[i]:
                self.best_err[i] = err
                self.best_m[i] = round_idx + 1
        return bool(self.done.all())


class GBTClassifier(_GbtParams, CheckpointParams, ClassifierEstimator):
    def __init__(self, mesh=None, **kwargs):
        super().__init__(**kwargs)
        self._mesh = mesh

    def _fit(self, frame: Frame) -> "GBTClassificationModel":
        mesh = self._mesh or get_default_mesh()
        val_col = self.getValidationIndicatorCol()
        X_val = y_val = w_val = None
        if val_col:
            vmask = np.asarray(frame[val_col]).astype(bool)
            if not vmask.any() or vmask.all():
                raise ValueError(
                    "validationIndicatorCol must mark a non-empty proper "
                    "subset of rows"
                )
            X_val, y_val, w_val = self._extract(frame.filter(vmask))
            frame = frame.filter(~vmask)
        with span("gbt.extract", module=_MODULE):
            X, y, w = self._extract(frame)
        n, F = X.shape
        y_max = int(y.max(initial=0))
        if y_val is not None:
            # validation rows must satisfy the binary contract too
            y_max = max(y_max, int(y_val.max(initial=0)))
        if y_max > 1:
            raise ValueError(
                "GBTClassifier is binary-only (Spark parity); wrap in "
                "OneVsRest for multiclass [B:10]"
            )
        n_bins = self.getMaxBins()
        n_rounds = self.getMaxIter()
        step = self.getStepSize()
        axis = mesh.axis_names[0]

        edges, xs, ys, ws, binned, grow_kwargs, round_mask = _prepare_boosting(
            self, X, y, w, mesh
        )
        y_signed = (2.0 * ys - 1.0).astype(jnp.float32)

        def round_weights(i):
            return jax.device_put(
                round_mask(i)[None, :], NamedSharding(mesh, P(None, axis))
            )

        # mid-fit round checkpointing (SURVEY.md §5.4): resume skips
        # completed boosting rounds, restoring trees and margins
        ckpt_dir = self.getCheckpointDir()
        interval = self.getCheckpointInterval()
        # NOTE: keep in lockstep with GBTRegressor._fit's checkpoint block
        # (gbt_regressor.py).  n_shards: saved arrays are padded to the
        # mesh size — a resume on a different mesh must restart cleanly.
        fingerprint = {
            "algo": "gbt", "maxIter": n_rounds, "maxDepth": self.getMaxDepth(),
            "n_shards": int(mesh.shape[axis]),
            "stepSize": step, "seed": self.getSeed(), "n_rows": n,
            "maxBins": n_bins,
            "subsamplingRate": float(self.getSubsamplingRate()),
            "minInstancesPerNode": float(self.getMinInstancesPerNode()),
            "minInfoGain": float(self.getMinInfoGain()),
            "featureSubsetStrategy": str(self.getFeatureSubsetStrategy()),
            "validation": bool(val_col),
            "validationTol": float(self.getValidationTol()),
        }
        tracker = (
            _ValidationTracker(self.getValidationTol()) if val_col else None
        )
        if val_col:
            X_val_j = jnp.asarray(X_val)
            y_signed_val = 2.0 * y_val.astype(np.float64) - 1.0
            margin_val = np.zeros(len(y_val), np.float64)
        features, thresholds, leaves, weights = [], [], [], []
        gains, counts = [], []
        margin = jnp.zeros(xs.shape[0], jnp.float32)
        start_round = 0
        if ckpt_dir and interval > 0:
            saved = _ckpt.load_state(ckpt_dir, fingerprint)
            # "gain" guards against state files written by older layouts:
            # a missing key means restart rather than crash mid-resume
            ok = saved is not None and int(saved["round"]) > 0 and "gain" in saved
            if ok and val_col and "val_done" not in saved:
                ok = False
            if ok:
                start_round = int(saved["round"])
                features = list(saved["feature"])
                thresholds = list(saved["threshold"])
                leaves = list(saved["leaf_stats"])
                weights = list(saved["tree_weights"])
                gains = list(saved["gain"])
                counts = list(saved["count"])
                margin = jnp.asarray(saved["margin"])
                if val_col:
                    margin_val = np.asarray(saved["val_margin"], np.float64)
                    tracker.best_err = np.asarray(
                        saved["val_best_err"], np.float64
                    ).reshape(1)
                    tracker.best_m = np.asarray(
                        saved["val_best_m"], np.int64
                    ).reshape(1)
                    tracker.done = np.asarray(
                        saved["val_done"], bool
                    ).reshape(1)
                    start_round = n_rounds if tracker.done[0] else start_round
        stopped = False
        for m in range(start_round, n_rounds):
            with span("gbt.round", round=m, trees=1, module=_MODULE):
                if m == 0:
                    row_stats = _label_stats(y_signed, ws)
                    tree_weight = 1.0
                else:
                    row_stats = _residual_stats(y_signed, ws, margin)
                    tree_weight = step
                forest = grow_forest(
                    binned, row_stats[None], round_weights(m), edges,
                    seed=self.getSeed() + m, mesh=mesh, **grow_kwargs,
                )
                margin = margin + tree_weight * _leaf_values(xs, forest)[0]
            _count_round("gbt_classifier", 1)
            features.append(forest.feature[0])
            thresholds.append(forest.threshold[0])
            leaves.append(forest.leaf_stats[0])
            gains.append(forest.gain[0])
            counts.append(forest.count[0])
            weights.append(tree_weight)
            if val_col:
                contrib_val = _leaf_values(X_val_j, forest)[0]
                margin_val = margin_val + tree_weight * np.asarray(
                    contrib_val, np.float64
                )
                err = _validation_error(margin_val, y_signed_val, w_val)
                if tracker.update(m, err):
                    stopped = True
            if ckpt_dir and interval > 0 and (m + 1) % interval == 0:
                state = {
                    "round": m + 1,
                    "feature": np.stack(features),
                    "threshold": np.stack(thresholds),
                    "leaf_stats": np.stack(leaves),
                    "gain": np.stack(gains),
                    "count": np.stack(counts),
                    "tree_weights": np.asarray(weights, np.float32),
                    "margin": np.asarray(margin),
                }
                if val_col:
                    state["val_margin"] = margin_val
                    state["val_best_err"] = tracker.best_err
                    state["val_best_m"] = tracker.best_m
                    state["val_done"] = tracker.done
                _ckpt.save_state(ckpt_dir, state, fingerprint)
            if stopped:
                break

        if val_col:
            keep = int(tracker.best_m[0])
            features, thresholds = features[:keep], thresholds[:keep]
            leaves, weights = leaves[:keep], weights[:keep]
            gains, counts = gains[:keep], counts[:keep]
        if ckpt_dir and interval > 0:
            _ckpt.clear_state(ckpt_dir)
        ensemble = Forest(
            feature=np.stack(features),
            threshold=np.stack(thresholds),
            leaf_stats=np.stack(leaves),
            max_depth=self.getMaxDepth(),
            gain=np.stack(gains),
            count=np.stack(counts),
        )
        model = GBTClassificationModel(
            forest=ensemble,
            tree_weights=np.asarray(weights, np.float32),
            n_features=F,
        )
        model.setParams(
            **{k2: v for k2, v in self.paramValues().items() if model.hasParam(k2)}
        )
        # Spark 3.1+ BinaryGBTClassifierTrainingSummary (GBT is
        # binary-only upstream and here; OvR wraps it for 15 classes)
        from sntc_tpu.models.summary import (
            BinaryClassificationTrainingSummary,
        )

        model.summary = BinaryClassificationTrainingSummary(
            [], len(weights), model, frame,
            labelCol=self.getLabelCol(), mesh=mesh,
        )
        return model


@partial(jax.jit, static_argnames=("max_depth", "traversal"))
def _gbt_margin(X, feature, threshold, leaf_stats, tree_weights, *,
                max_depth, traversal="xla"):
    from sntc_tpu.kernels.forest import traverse_forest

    stats = traverse_forest(
        X, feature, threshold, leaf_stats, max_depth=max_depth,
        traversal=traversal,
    )  # [M, N, 3]
    values = stats[..., 1] / jnp.maximum(stats[..., 0], 1e-12)  # [M, N]
    return jnp.einsum("m,mn->n", tree_weights, values)


@partial(jax.jit, static_argnames=("max_depth", "traversal"))
def _ovr_fused_raw(X, feature, threshold, leaf_stats, sel, *, max_depth,
                   traversal="xla"):
    """Fused OneVsRest(GBT) raw scores: ONE traversal of all K classes'
    trees (concatenated on the tree axis) + a [K, M] class-selection
    contraction — K device dispatches per serving batch become one."""
    from sntc_tpu.kernels.forest import traverse_forest

    stats = traverse_forest(
        X, feature, threshold, leaf_stats, max_depth=max_depth,
        traversal=traversal,
    )  # [M, N, 3]
    values = stats[..., 1] / jnp.maximum(stats[..., 0], 1e-12)  # [M, N]
    margins = sel @ values  # [K, N]
    return (2.0 * margins).T  # raw class-1 score = 2F


@partial(jax.jit, static_argnames=("max_depth", "mode", "traversal"))
def _gbt_serve(
    X, feature, threshold, leaf_stats, tree_weights, thr, *, max_depth,
    mode, traversal="xla"
):
    """Traverse + margin + sigmoid + predict, packed: one dispatch and one
    device→host transfer per serving micro-batch."""
    from sntc_tpu.models.base import pack_serve_outputs

    m = _gbt_margin(
        X, feature, threshold, leaf_stats, tree_weights,
        max_depth=max_depth, traversal=traversal,
    )
    raw = jnp.stack([-2.0 * m, 2.0 * m], axis=1)
    p1 = jax.nn.sigmoid(2.0 * m)
    prob = jnp.stack([1.0 - p1, p1], axis=1)
    return pack_serve_outputs(raw, prob, thr, mode)


class GBTClassificationModel(_GbtParams, ForestDeviceMixin, ClassificationModel):
    def __init__(self, forest: Forest, tree_weights: np.ndarray,
                 n_features: int = 0, **kwargs):
        super().__init__(**kwargs)
        self.forest = forest
        self.treeWeights = np.asarray(tree_weights, np.float32)
        self._n_features = int(n_features)

    def _forest_arrays(self) -> tuple:
        return super()._forest_arrays() + (self.treeWeights,)

    @property
    def num_classes(self) -> int:
        return 2

    @property
    def numTrees(self) -> int:
        """Trees kept — ``< maxIter`` after a validated-boosting stop."""
        return int(len(self.treeWeights))

    def _save_extra(self):
        return (
            {"max_depth": self.forest.max_depth,
             "n_features": self._n_features},
            {
                "feature": self.forest.feature,
                "threshold": self.forest.threshold,
                "leaf_stats": self.forest.leaf_stats,
                "gain": self.forest.gain,
                "count": self.forest.count,
                "tree_weights": self.treeWeights,
            },
        )

    @classmethod
    def _load_from(cls, params, extra, arrays):
        forest = Forest(
            arrays["feature"], arrays["threshold"], arrays["leaf_stats"],
            int(extra["max_depth"]),
            arrays.get("gain"), arrays.get("count"),
        )
        m = cls(
            forest=forest,
            tree_weights=arrays["tree_weights"],
            n_features=int(extra.get("n_features", 0)),
        )
        m.setParams(**params)
        return m

    @property
    def featureImportances(self) -> np.ndarray:
        n = self._n_features or int(self.forest.feature.max()) + 1
        # Spark's GBTClassificationModel passes perTreeNormalization=false
        return self.forest.feature_importances(
            n, per_tree_normalization=False
        )

    def margin(self, X: np.ndarray) -> np.ndarray:
        return np.asarray(
            _gbt_margin(
                jnp.asarray(X),
                *self._device_forest(),
                max_depth=self.forest.max_depth,
            )
        )

    def _raw_predict(self, X: np.ndarray) -> np.ndarray:
        m = self.margin(X)
        return np.stack([-2.0 * m, 2.0 * m], axis=1)

    def _raw_to_probability(self, raw: np.ndarray) -> np.ndarray:
        p1 = 1.0 / (1.0 + np.exp(-raw[:, 1]))
        return np.stack([1.0 - p1, p1], axis=1)

    def _predict_all_dev(self, X: np.ndarray):
        from sntc_tpu.kernels import serve_kernel_call

        mode, thr = self._threshold_mode()
        Xd = jnp.asarray(X)
        fa, ta, ls, tw = self._device_forest()
        md = self.forest.max_depth

        def run(traversal):
            return _gbt_serve(
                Xd, fa, ta, ls, tw, jnp.asarray(thr),
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


def fit_gbt_ovr_vectorized(
    classifier: "GBTClassifier",
    X: np.ndarray,
    y: np.ndarray,
    w: np.ndarray,
    num_classes: int,
    mesh,
    val_mask: Optional[np.ndarray] = None,
) -> list:
    """All K one-vs-rest binary GBT fits in ONE boosting loop [B:10].

    The class axis rides the grower's tree axis: every round grows K trees
    over the SAME binned features with per-class residual stats
    (``row_stats[K, 3, N]``, rows along lanes) — K× fewer level passes, host syncs, and
    binning passes than OneVsRest's sequential sub-fits, and the K-wide
    histograms batch better on the MXU (SURVEY.md §7.2 item 4).

    Exactly reproduces the sequential fits when ``featureSubsetStrategy=
    "all"`` (the GBT default): the per-round subsampling mask is shared
    across classes, matching sequential OneVsRest where every class copy
    carries the same seed.  With feature subsetting the per-class random
    subsets differ from the sequential run (documented deviation).

    Validated boosting (``val_mask`` rows held out, Spark
    ``runWithValidation``): classes stop **per-class** — each class keeps
    its own ``best_m`` trees — while the joint loop runs until every class
    has plateaued (trees grown for already-done classes are discarded at
    truncation), exactly matching the sequential per-class sub-fits.

    Returns a list of K fitted :class:`GBTClassificationModel`.
    """
    if val_mask is not None:
        val_mask = np.asarray(val_mask).astype(bool)
        if not val_mask.any() or val_mask.all():
            raise ValueError(
                "validationIndicatorCol must mark a non-empty proper "
                "subset of rows"
            )
        X_val, y_val, w_val = X[val_mask], y[val_mask], w[val_mask]
        X, y, w = X[~val_mask], y[~val_mask], w[~val_mask]
    n, F = X.shape
    K = int(num_classes)
    n_rounds = classifier.getMaxIter()
    step = classifier.getStepSize()
    seed = classifier.getSeed()
    axis = mesh.axis_names[0]

    edges, xs, ys, ws, binned, grow_kwargs, round_mask = _prepare_boosting(
        classifier, X, y, w, mesh
    )
    tracker = None
    if val_mask is not None:
        tracker = _ValidationTracker(classifier.getValidationTol(), k=K)
        X_val_j = jnp.asarray(X_val)
        ks = np.arange(K)[:, None]
        y_signed_val = (
            2.0 * (y_val[None, :] == ks) - 1.0
        ).astype(np.float64)  # [K, Nv]
        margins_val = np.zeros((K, len(y_val)), np.float64)
    n_pad = xs.shape[0]
    y_signed = _ovr_signed_labels(ys, num_classes=K)  # [K, Np]
    row_sharding = NamedSharding(mesh, P(None, axis))

    def round_weights(i):
        # one [n_pad] host->device transfer; the K-way copy happens
        # on-device (no K redundant host buffers on the fit hot loop)
        return _broadcast_classes(
            jax.device_put(round_mask(i), NamedSharding(mesh, P(axis))),
            num_classes=K,
        )

    margins = jax.device_put(np.zeros((K, n_pad), np.float32), row_sharding)
    feats, thrs, lvs, gns, cnts, wts = [], [], [], [], [], []
    for m in range(n_rounds):
        with span("gbt.round", round=m, trees=K, module=_MODULE):
            if m == 0:
                row_stats = _label_stats(y_signed, ws)  # [K, 3, Np]
                tree_weight = 1.0
            else:
                row_stats = _residual_stats(y_signed, ws, margins)
                tree_weight = step
            forest = grow_forest(
                binned, row_stats, round_weights(m), edges,
                seed=seed + m, mesh=mesh, **grow_kwargs,
            )
            margins = margins + tree_weight * _leaf_values(xs, forest)
        _count_round("gbt_ovr", K)
        feats.append(forest.feature)
        thrs.append(forest.threshold)
        lvs.append(forest.leaf_stats)
        gns.append(forest.gain)
        cnts.append(forest.count)
        wts.append(tree_weight)
        if tracker is not None:
            contribs_val = _leaf_values(X_val_j, forest)  # [K, Nv]
            margins_val = margins_val + tree_weight * np.asarray(
                contribs_val, np.float64
            )
            errs = _validation_error(margins_val, y_signed_val, w_val)
            if tracker.update(m, errs):
                break

    tree_weights = np.asarray(wts, np.float32)
    models = []
    for c in range(K):
        keep = int(tracker.best_m[c]) if tracker is not None else len(feats)
        ensemble = Forest(
            feature=np.stack([f[c] for f in feats[:keep]]),
            threshold=np.stack([t[c] for t in thrs[:keep]]),
            leaf_stats=np.stack([l[c] for l in lvs[:keep]]),
            max_depth=classifier.getMaxDepth(),
            gain=np.stack([g[c] for g in gns[:keep]]),
            count=np.stack([ct[c] for ct in cnts[:keep]]),
        )
        model = GBTClassificationModel(
            forest=ensemble, tree_weights=tree_weights[:keep], n_features=F,
        )
        model.setParams(
            **{
                k2: v
                for k2, v in classifier.paramValues().items()
                if model.hasParam(k2)
            }
        )
        models.append(model)
    return models
