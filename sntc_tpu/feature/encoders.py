"""OneHotEncoder / VectorSlicer / ElementwiseProduct.

Behavioral spec: upstream ``ml/feature/{OneHotEncoder,VectorSlicer,
ElementwiseProduct}.scala`` [U]:

  * OneHotEncoder: fit learns each input column's category count (max
    index + 1); transform maps index ``i`` to a one-hot vector.
    ``dropLast`` (default True) drops the final category (the all-zeros
    encoding, Spark's reference-level convention); ``handleInvalid``
    error (default) / keep (extra all-"invalid" category appended).
    Multi-column; output vectors are concatenated per column.
  * VectorSlicer: stateless gather of ``indices`` from a vector column.
  * ElementwiseProduct: stateless Hadamard product with ``scalingVec``.

TPU note: one-hot output feeds the estimators as a dense ``[N, D]``
block (XLA consumes dense one-hots natively — the MXU matmul against a
one-hot IS the gather); host-side the encoding is a single fancy-index
assignment per column.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

from sntc_tpu.core.base import Estimator, Model, Transformer
from sntc_tpu.core.frame import Frame
from sntc_tpu.core.params import Param, validators
from sntc_tpu.feature.selection import take_columns


class _OheParams:
    inputCols = Param("input index columns", default=None)
    outputCols = Param("output vector columns (same length)", default=None)
    dropLast = Param(
        "drop the last category (all-zeros encoding)", default=True,
        validator=validators.is_bool(),
    )
    handleInvalid = Param(
        "unseen-index handling: error | keep (extra category)",
        default="error",
        validator=validators.one_of("error", "keep"),
    )

    def _cols(self):
        ins = self.getInputCols()
        outs = self.getOutputCols()
        if not ins:
            raise ValueError("inputCols is required")
        outs = outs or [c + "_ohe" for c in ins]
        if len(ins) != len(outs):
            raise ValueError("inputCols and outputCols lengths differ")
        return ins, outs


class OneHotEncoder(_OheParams, Estimator):
    def _fit(self, frame: Frame) -> "OneHotEncoderModel":
        ins, _ = self._cols()
        sizes = []
        for c in ins:
            v = np.asarray(frame[c], np.float64)
            if len(v) and ((v < 0) | (v != np.floor(v))).any():
                raise ValueError(
                    f"OneHotEncoder: column {c!r} must hold non-negative "
                    "integer indices"
                )
            sizes.append(int(v.max()) + 1 if len(v) else 0)
        model = OneHotEncoderModel(categorySizes=sizes)
        model.setParams(**self.paramValues())
        return model


class OneHotEncoderModel(_OheParams, Model):
    def __init__(self, categorySizes: Sequence[int] = (), **kwargs):
        super().__init__(**kwargs)
        self.categorySizes = [int(s) for s in categorySizes]

    def _save_extra(self):
        return {"categorySizes": self.categorySizes}, {}

    @classmethod
    def _load_from(cls, params, extra, arrays):
        m = cls(categorySizes=extra["categorySizes"])
        m.setParams(**params)
        return m

    def transform(self, frame: Frame) -> Frame:
        ins, outs = self._cols()
        drop = self.getDropLast()
        keep_invalid = self.getHandleInvalid() == "keep"
        out = frame
        for c, o, size in zip(ins, outs, self.categorySizes):
            idx = np.asarray(frame[c], np.int64)
            n = len(idx)
            invalid = (idx < 0) | (idx >= size)
            if invalid.any() and not keep_invalid:
                raise ValueError(
                    f"OneHotEncoder: column {c!r} has indices outside "
                    f"[0, {size}) (set handleInvalid='keep')"
                )
            # width: size (+1 invalid slot when keeping) (−1 when dropLast)
            width = size + (1 if keep_invalid else 0) - (1 if drop else 0)
            enc = np.zeros((n, max(width, 0)), np.float32)
            slot = np.where(invalid, size if keep_invalid else 0, idx)
            ok = slot < width  # dropLast: the last category stays all-zero
            rows = np.flatnonzero(ok)
            enc[rows, slot[rows]] = 1.0
            out = out.with_column(o, enc)
        return out


class VectorSlicer(Transformer):
    """Column gather from a vector column — stateless."""

    inputCol = Param("input vector column", default="features")
    outputCol = Param("output vector column", default="sliced")
    indices = Param("indices to keep, in output order", default=None)

    def transform(self, frame: Frame) -> Frame:
        idx = self.getIndices()
        if not idx:
            raise ValueError("indices is required")
        X = frame[self.getInputCol()]
        idx = np.asarray(idx, np.int64)
        if (idx < 0).any() or (idx >= X.shape[1]).any():
            raise ValueError(
                f"indices out of range for vector width {X.shape[1]}"
            )
        return frame.with_column(self.getOutputCol(), take_columns(X, idx))


class ElementwiseProduct(Transformer):
    """Hadamard product with a fixed scaling vector — stateless."""

    inputCol = Param("input vector column", default="features")
    outputCol = Param("output vector column", default="scaled")
    scalingVec = Param("the per-dimension multiplier vector", default=None)

    def transform(self, frame: Frame) -> Frame:
        w = self.getScalingVec()
        if w is None:
            raise ValueError("scalingVec is required")
        X = frame[self.getInputCol()]
        w = np.asarray(w, np.float32)
        if w.shape != (X.shape[1],):
            raise ValueError(
                f"scalingVec length {w.shape[0]} != vector width {X.shape[1]}"
            )
        return frame.with_column(
            self.getOutputCol(), (X * w[None, :]).astype(np.float32)
        )
