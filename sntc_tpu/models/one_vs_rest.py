"""OneVsRest — K binary reductions of a multiclass problem [B:10].

Behavioral spec: SURVEY.md §2.3 (upstream ``ml/classification/OneVsRest.
scala`` [U]): fit one copy of the base classifier per class on relabeled
{rest=0, class=1} data; prediction = argmax over per-class raw class-1
scores; ``parallelism`` is accepted for API parity (the fits are sequential
here — each inner fit already saturates the TPU mesh; Spark's thread pool
existed to overlap JVM scheduling, not compute).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from sntc_tpu.core.frame import Frame
from sntc_tpu.core.params import Param, validators
from sntc_tpu.obs import module_of, span
from sntc_tpu.models.base import (
    ClassificationModel,
    ClassifierEstimator,
    ClassifierParams,
)


def _build_fused_ovr(models):
    """A ``f(X) -> [N, K]`` fused raw-score closure for homogeneous
    sub-models, or None (see ``OneVsRestModel._fused_raw``)."""
    from sntc_tpu.models.linear_svc import LinearSVCModel
    from sntc_tpu.models.logistic_regression import LogisticRegressionModel
    from sntc_tpu.models.tree.gbt import GBTClassificationModel

    if not models:
        return None
    if all(isinstance(m, LinearSVCModel) for m in models):
        # margins stack into one [D, K] f32 matmul, exactly the LR case
        WT = np.stack([m.coefficients for m in models]).T.astype(np.float32)
        b = np.asarray([m.intercept for m in models], np.float32)

        def svc_fused(X):
            return X.astype(np.float32, copy=False) @ WT + b

        return svc_fused
    if all(
        isinstance(m, LogisticRegressionModel) and m.is_binomial
        for m in models
    ):
        # [D, K] f32 once at build time; predict is one f32 host matmul
        # (tiny weights, raw margins only — cheaper than K device round
        # trips at any batch size, no f64 copy of the batch).  Margin is
        # the class-1/class-0 row DIFFERENCE — same as the per-model
        # loop's raw(1), which never assumes row 0 is zero (it isn't for
        # e.g. externally-constructed symmetric [-w, w] models)
        WT = np.stack(
            [m.coefficientMatrix[1] - m.coefficientMatrix[0] for m in models]
        ).T.astype(np.float32)
        b = np.asarray(
            [m.interceptVector[1] - m.interceptVector[0] for m in models],
            np.float32,
        )

        def lr_fused(X):
            return X.astype(np.float32, copy=False) @ WT + b

        return lr_fused
    if all(isinstance(m, GBTClassificationModel) for m in models) and (
        len({m.forest.max_depth for m in models}) == 1
    ):
        import jax.numpy as jnp

        from sntc_tpu.models.tree.gbt import _ovr_fused_raw

        feature = np.concatenate([m.forest.feature for m in models])
        threshold = np.concatenate([m.forest.threshold for m in models])
        leaf_stats = np.concatenate([m.forest.leaf_stats for m in models])
        K = len(models)
        M = feature.shape[0]
        sel = np.zeros((K, M), np.float32)
        off = 0
        for c, m in enumerate(models):
            t = m.forest.feature.shape[0]
            sel[c, off : off + t] = m.treeWeights
            off += t
        max_depth = models[0].forest.max_depth
        dev = tuple(
            jnp.asarray(a) for a in (feature, threshold, leaf_stats, sel)
        )

        def gbt_fused(X):
            return np.asarray(
                _ovr_fused_raw(jnp.asarray(X), *dev, max_depth=max_depth)
            )

        return gbt_fused
    return None


_MODULE = module_of(__name__)


class _OvrParams(ClassifierParams):
    parallelism = Param(
        "API parity only; inner fits already saturate the mesh",
        default=1,
        validator=validators.gteq(1),
    )


class OneVsRest(_OvrParams, ClassifierEstimator):
    def __init__(self, classifier=None, mesh=None, **kwargs):
        super().__init__(**kwargs)
        if classifier is None:
            raise ValueError("OneVsRest requires a classifier estimator")
        self.classifier = classifier
        self._mesh = mesh

    def _fit(self, frame: Frame) -> "OneVsRestModel":
        with span("ovr.extract", module=_MODULE):
            X, y, w = self._extract(frame)
            k = int(y.max()) + 1
        bin_col = f"ovr_label_{self.uid}"
        overrides = {
            "labelCol": bin_col,
            "featuresCol": self.getFeaturesCol(),
        }
        # forward sample weights to every binary sub-fit (Spark parity)
        if self.getWeightCol() and self.classifier.hasParam("weightCol"):
            overrides["weightCol"] = self.getWeightCol()
        models: List[ClassificationModel] = self._fit_vectorized(
            X, y, w, k, frame
        )
        if models is not None:
            # persisted metadata must be path-independent: vectorized
            # sub-models carry the same column overrides the sequential
            # sub-fits get via classifier.copy(overrides)
            for sub in models:
                sub.setParams(
                    **{k2: v for k2, v in overrides.items() if sub.hasParam(k2)}
                )
        else:
            models = []
            for c in range(k):
                y_c = (y == c).astype(np.float64)
                sub = frame.with_column(bin_col, y_c)
                models.append(self.classifier.copy(overrides).fit(sub))
        model = OneVsRestModel(models=models)
        model.setParams(
            **{k2: v for k2, v in self.paramValues().items() if model.hasParam(k2)}
        )
        return model

    def _fit_vectorized(self, X, y, w, k, frame):
        """All-classes-at-once fit when the base classifier supports riding
        a batched class axis (GBT: K trees per boosting round over the
        same binned features — SURVEY.md §7.2 item 4; LogisticRegression:
        K binary LBFGS lanes relabeled in-program).  Returns None when the
        classifier has no vectorized path or mid-fit checkpointing is
        requested (the sequential path owns that)."""
        from sntc_tpu.models.logistic_regression import LogisticRegression
        from sntc_tpu.models.tree.gbt import GBTClassifier, fit_gbt_ovr_vectorized
        from sntc_tpu.parallel.context import get_default_mesh

        if not isinstance(
            self.classifier, (LogisticRegression, GBTClassifier)
        ):
            return None
        # a weightCol set on the classifier itself (not this OvR) refers to
        # a column of the relabeled sub-frame — only the sequential path
        # reproduces that
        if self.classifier.getWeightCol() and not self.getWeightCol():
            return None
        mesh = self._mesh or self.classifier._mesh or get_default_mesh()

        if isinstance(self.classifier, LogisticRegression):
            if not self.classifier.supports_vectorized_ovr():
                return None
            return self.classifier._fit_ovr_lanes(X, y, w, k, mesh)
        # sequential only when checkpointing would actually happen (both
        # interval AND dir set — matching GBTClassifier._fit's own gate)
        if (
            self.classifier.getCheckpointInterval() > 0
            and self.classifier.getCheckpointDir()
        ):
            return None
        # validated boosting: the indicator column lives on the input frame
        vcol = self.classifier.getValidationIndicatorCol()
        val_mask = np.asarray(frame[vcol]).astype(bool) if vcol else None
        return fit_gbt_ovr_vectorized(
            self.classifier, X, y, w, k, mesh, val_mask=val_mask
        )

    def _sub_stages(self):
        return [self.classifier]

    @classmethod
    def _from_sub_stages(cls, stages, params, extra=None):
        obj = cls(classifier=stages[0])
        obj.setParams(**params)
        return obj


class OneVsRestModel(_OvrParams, ClassificationModel):
    def __init__(self, models: Optional[List[ClassificationModel]] = None, **kwargs):
        super().__init__(**kwargs)
        self.models = list(models or [])
        # lazy (models-identity-key, closure-or-False); keyed so mutating
        # ``self.models`` (public list) invalidates instead of serving the
        # stale fused weights
        self._fused = None

    @property
    def num_classes(self) -> int:
        return len(self.models)

    def _sub_stages(self):
        return self.models

    @classmethod
    def _from_sub_stages(cls, stages, params, extra=None):
        obj = cls(models=stages)
        obj.setParams(**params)
        return obj

    def _fused_raw(self):
        """Fused per-class raw scores — K sub-model predicts collapse into
        ONE pass when the sub-models are homogeneous:

        * LogisticRegression: the K binary coefficient rows stack into a
          single ``[K, D]`` matrix — raw is one matmul;
        * GBT: the K forests concatenate along the TREE axis; one
          traversal of all M trees + a ``[K, M]`` class-selection matmul
          yields every class's margin (one device dispatch instead of K).

        Mixed/unknown sub-model types fall back to the per-model loop.
        """
        # key on the model OBJECTS (kept alive by the tuple — identity
        # comparison; id() alone could be reused after GC)
        models = tuple(self.models)
        if self._fused is None or len(self._fused[0]) != len(models) or any(
            a is not b for a, b in zip(self._fused[0], models)
        ):
            self._fused = (models, _build_fused_ovr(self.models) or False)
        return self._fused[1] or None

    def _raw_predict(self, X: np.ndarray) -> np.ndarray:
        fused = self._fused_raw()
        if fused is not None:
            return fused(X)
        # per-class raw class-1 margin (Spark uses rawPrediction(1))
        cols = [m._raw_predict(X)[:, 1] for m in self.models]
        return np.stack(cols, axis=1)

    def _raw_to_probability(self, raw: np.ndarray) -> np.ndarray:
        # Spark OvR emits no probability column; we provide a normalized
        # softmax-free score for API convenience (documented extension)
        shifted = raw - raw.max(axis=1, keepdims=True)
        e = np.exp(shifted)
        return e / e.sum(axis=1, keepdims=True)

    def _prob_to_prediction(self, prob: np.ndarray) -> np.ndarray:
        return np.argmax(prob, axis=1).astype(np.float64)

    def transform(self, frame: Frame) -> Frame:
        X = frame[self.getFeaturesCol()].astype(np.float32, copy=False)
        raw = self._raw_predict(X)
        out = frame
        if self.getRawPredictionCol():
            out = out.with_column(self.getRawPredictionCol(), raw)
        if self.getPredictionCol():
            out = out.with_column(
                self.getPredictionCol(),
                np.argmax(raw, axis=1).astype(np.float64),
            )
        return out
