"""Shared classifier plumbing — the ``ProbabilisticClassifier`` analog.

Behavioral spec: Spark's classifier hierarchy (upstream
``ml/classification/{Classifier,ProbabilisticClassifier}.scala`` [U],
SURVEY.md §3.4): every model's ``transform`` appends ``rawPrediction``
(margins), ``probability`` and ``prediction`` (float64 index) columns; binary
models honor ``threshold``.

Subclass models implement ``_raw_predict(X) -> [N, K]`` margins (device
compute, jitted by the subclass) and ``_raw_to_probability``.
"""

from __future__ import annotations

import numpy as np

from sntc_tpu.core.base import Estimator, Model
from sntc_tpu.core.frame import Frame
from sntc_tpu.core.params import Param, validators
from sntc_tpu.obs.metrics import inc


class ClassifierParams:
    featuresCol = Param("feature vector column", default="features")
    labelCol = Param("label index column", default="label")
    predictionCol = Param("output prediction column", default="prediction")
    rawPredictionCol = Param("output margins column", default="rawPrediction")
    probabilityCol = Param("output probability column", default="probability")


class CheckpointParams:
    """Mid-fit checkpoint/resume (SURVEY.md §5.4 — beyond Spark parity)."""

    checkpointInterval = Param(
        "persist optimizer state every N iterations/boosting rounds "
        "(-1 = off); a re-run fit with the same checkpointDir resumes",
        default=-1,
    )
    checkpointDir = Param("directory for mid-fit optimizer state", default=None)


class ClassifierEstimator(ClassifierParams, Estimator):
    """Base estimator: extracts (X, y, w) from the frame."""

    weightCol = Param("optional row weight column", default=None)

    def _extract(self, frame: Frame):
        X = frame[self.getFeaturesCol()]
        if X.ndim != 2:
            raise ValueError(
                f"featuresCol {self.getFeaturesCol()!r} must be a vector "
                "column (use VectorAssembler)"
            )
        X = X.astype(np.float32, copy=False)
        y_raw = frame[self.getLabelCol()].astype(np.float64)
        y = y_raw.astype(np.int32)
        if not np.array_equal(y_raw, y.astype(np.float64)) or (y < 0).any():
            raise ValueError("labelCol must contain non-negative integer indices")
        wcol = self.getWeightCol()
        w = (
            frame[wcol].astype(np.float32)
            if wcol
            else np.ones(len(y), dtype=np.float32)
        )
        return X, y, w


def pack_serve_outputs(raw, prob, thr, mode: str):
    """Traceable tail shared by every model's fused serve program:
    probability→prediction under ``mode`` (see ``_threshold_mode``), then
    raw|prob|prediction packed into ONE ``[N, 2K+1]`` array so a serving
    micro-batch costs a single device→host transfer."""
    import jax.numpy as jnp

    if mode == "thresholds":
        zero = thr == 0
        scaled = prob / jnp.where(zero, 1.0, thr)[None, :]
        scaled = jnp.where(
            zero[None, :],
            jnp.where(prob > 0, jnp.inf, -jnp.inf),
            scaled,
        )
        pred = jnp.argmax(scaled, axis=1)
    elif mode == "binary":
        pred = (prob[:, 1] > thr[0]).astype(jnp.int32)
    else:
        pred = jnp.argmax(prob, axis=1)
    return jnp.concatenate(
        [raw, prob, pred[:, None].astype(raw.dtype)], axis=1
    )


class ClassificationModel(ClassifierParams, Model):
    """Base fitted model: margins -> probability -> prediction columns."""

    threshold = Param(
        "binary decision threshold on P(class 1)",
        default=0.5,
        validator=validators.in_range(0.0, 1.0),
    )
    thresholds = Param(
        "per-class thresholds (length numClasses, at most one zero); "
        "prediction = argmax(probability[k] / thresholds[k]) — Spark "
        "ProbabilisticClassificationModel.probability2prediction",
        default=None,
    )

    @property
    def num_classes(self) -> int:
        raise NotImplementedError

    def _raw_predict(self, X: np.ndarray) -> np.ndarray:
        """Margins [N, K] (K=2 for binary: [-margin, margin], Spark-style)."""
        raise NotImplementedError

    def _raw_to_probability(self, raw: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def _predict_raw_prob(self, X: np.ndarray):
        """(raw, probability) for a feature matrix.  Subclasses override to
        fuse both into ONE device program (one dispatch per micro-batch on
        the serving hot path [B:11]); the default is the two-step path."""
        raw = self._raw_predict(X)
        return raw, self._raw_to_probability(raw)

    def _prob_to_prediction(self, prob: np.ndarray) -> np.ndarray:
        # one rule + one validation: _threshold_mode (shared with the
        # fused device serve programs)
        mode, thr = self._threshold_mode()
        if mode == "thresholds":
            ts = thr.astype(np.float64)
            zero = ts == 0
            scaled = prob / np.where(zero, 1.0, ts)
            # Spark: p/0 -> +inf when p > 0; a 0/0 class never wins
            scaled = np.where(
                zero[None, :],
                np.where(prob > 0, np.inf, -np.inf),
                scaled,
            )
            return np.argmax(scaled, axis=1).astype(np.float64)
        if mode == "binary":
            return (prob[:, 1] > thr[0]).astype(np.float64)
        return np.argmax(prob, axis=1).astype(np.float64)

    def _build_output(self, frame: Frame, raw, prob) -> Frame:
        out = frame
        if self.getRawPredictionCol():
            out = out.with_column(self.getRawPredictionCol(), raw)
        if self.getProbabilityCol():
            out = out.with_column(self.getProbabilityCol(), prob)
        if self.getPredictionCol():
            out = out.with_column(
                self.getPredictionCol(), self._prob_to_prediction(prob)
            )
        return out

    def transform(self, frame: Frame) -> Frame:
        X = frame[self.getFeaturesCol()].astype(np.float32, copy=False)
        rp = (
            self._predict_raw_prob_host(X)
            if X.shape[0] <= self._host_serve_rows()
            else None
        )
        inc(
            "sntc_predict_head_dispatch_total",
            path="device" if rp is None else "host",
        )
        if rp is None:
            rp = self._predict_raw_prob(X)
        return self._build_output(frame, *rp)

    def _threshold_mode(self):
        """(mode, thr) describing the probability→prediction rule, with
        the same validation as :meth:`_prob_to_prediction` — ``mode`` is a
        static program variant, ``thr`` its parameter vector."""
        ts = self.getThresholds()
        if ts is not None:
            ts = np.asarray(ts, np.float64)
            if ts.shape != (self.num_classes,):
                raise ValueError(
                    f"thresholds length {ts.shape} must equal "
                    f"numClasses {self.num_classes}"
                )
            if (ts < 0).any() or (ts == 0).sum() > 1:
                raise ValueError(
                    "thresholds must be non-negative with at most one zero"
                )
            return "thresholds", ts.astype(np.float32)
        if self.num_classes == 2:
            return "binary", np.asarray([self.getThreshold()], np.float32)
        return "argmax", np.zeros(1, np.float32)

    def _predict_all_dev(self, X: np.ndarray):
        """Optional one-dispatch device path: a PACKED ``[N, 2K+1]`` device
        array of ``raw | prob | prediction`` columns (one device→host
        transfer materializes everything), or None when this model has no
        fused device program (callers fall back to the sync transform)."""
        return None

    def has_device_serve(self) -> bool:
        """True when ``_predict_all_dev`` returns a real packed program
        for THIS model — the static capability the fusion planner
        (``sntc_tpu.fuse``) checks before fusing a head into a segment.
        Subclasses whose device path is conditional (e.g. gaussian
        NaiveBayes) must override; ``_predict_all_dev`` must never
        return None when this returns True."""
        return (
            type(self)._predict_all_dev
            is not ClassificationModel._predict_all_dev
        )

    def _predict_raw_prob_host(self, X: np.ndarray):
        """Optional pure-host (numpy) predict path, or None.  Used for
        micro-batches at or below the host-serve crossover
        (:meth:`_host_serve_rows`)."""
        return None

    @staticmethod
    def _host_serve_rows() -> int:
        """Row count at or below which a model with a numpy predict
        path serves on the host: ``SNTC_SERVE_HOST_ROWS``, default 0 —
        the device serves every batch.  Where the dispatch + transfer
        round trip outweighs a tiny model's FLOPs on the local chip has
        not been measured; a crossover is a number to derive there,
        not a default to inherit."""
        import os

        return int(os.environ.get("SNTC_SERVE_HOST_ROWS", 0))

    def transform_async(self, frame: Frame):
        """One fused device dispatch; host materialization deferred to the
        returned finalize (see Transformer.transform_async).  Batches at
        or below the host-serve crossover (off by default) take the
        pure-host path instead WHEN the model has one (``transform``
        applies the same placement rule)."""
        X = frame[self.getFeaturesCol()].astype(np.float32, copy=False)
        if X.shape[0] <= self._host_serve_rows():
            rp = self._predict_raw_prob_host(X)
            if rp is not None:
                inc("sntc_predict_head_dispatch_total", path="host")
                out = self._build_output(frame, *rp)
                return lambda: out
        dev = self._predict_all_dev(X)
        if dev is None:
            out = self.transform(frame)
            return lambda: out
        inc("sntc_predict_head_dispatch_total", path="device")

        def finalize():
            packed = np.asarray(dev)
            k = self.num_classes
            out = frame
            if self.getRawPredictionCol():
                out = out.with_column(
                    self.getRawPredictionCol(), packed[:, :k]
                )
            if self.getProbabilityCol():
                out = out.with_column(
                    self.getProbabilityCol(), packed[:, k : 2 * k]
                )
            if self.getPredictionCol():
                out = out.with_column(
                    self.getPredictionCol(),
                    packed[:, 2 * k].astype(np.float64),
                )
            return out

        return finalize

    def predict(self, X: np.ndarray) -> np.ndarray:
        """Convenience: prediction indices for a raw feature matrix."""
        prob = self._raw_to_probability(self._raw_predict(X))
        return self._prob_to_prediction(prob)
