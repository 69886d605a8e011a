"""StringIndexer / IndexToString — label string <-> index encoding.

Behavioral spec: SURVEY.md §2.2 (upstream ``ml/feature/StringIndexer.scala``
[U]).  Ordering parity matters for macro-F1 parity (SURVEY.md §7.2 item 3):
the default ``frequencyDesc`` orders labels by descending frequency with ties
broken by the string ascending — reproduced exactly here.  ``handleInvalid``:
``error`` | ``skip`` (drop unseen rows) | ``keep`` (unseen -> index
``len(labels)``).  Output indices are float64, as in Spark.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from sntc_tpu.core.base import Estimator, Model, Transformer
from sntc_tpu.core.frame import Frame
from sntc_tpu.core.params import Param, validators
from sntc_tpu.obs import inc

_PASSES = "sntc_feature_index_passes_total"


def _hash_labels(values: np.ndarray) -> Tuple[np.ndarray, List[str]]:
    """One hash pass over a column: ``(codes, keys)`` with
    ``keys[codes[i]] == str(values[i])`` for every row.  Distinct values
    may share a key (``1`` and ``"1"``): the callers merge by key, as
    ``str()`` does.  Per-row Python runs only on the NA rows of an object
    column (``None`` and ``nan`` stay the labels ``"None"`` and ``"nan"``)
    and on object columns whose values can compare equal under different
    ``str()`` (``1``, ``1.0`` and ``True``; ``0.0`` and ``-0.0``), which
    are stringified whole."""
    import pandas as pd

    values = np.asarray(values)
    kind = values.dtype.kind
    if values.ndim == 1 and kind == "f":
        # by bit pattern: -0.0 and 0.0 stay two keys, as str() makes them;
        # the NaN payloads are merged by their one key "nan"
        bits = values.view(f"i{values.dtype.itemsize}")
        codes, uniques = pd.factorize(bits)
        uniques = np.asarray(uniques).astype(bits.dtype).view(values.dtype)
        return codes, [str(u) for u in uniques]
    if values.ndim == 1 and kind in "iub":
        codes, uniques = pd.factorize(values)
        return codes, [str(u) for u in uniques]
    if values.ndim != 1 or kind not in "OU":
        values = np.array([str(v) for v in values], dtype=object)
    codes, uniques = pd.factorize(values, use_na_sentinel=True)
    if not _prints_as_hashed(uniques):
        return _hash_labels(np.array([str(v) for v in values], dtype=object))
    keys = [str(u) for u in uniques]
    na = np.flatnonzero(codes < 0)
    if len(na):
        na_codes, na_keys = pd.factorize(
            np.array([str(v) for v in values[na]], dtype=object)
        )
        codes[na] = na_codes + len(keys)
        keys += list(na_keys)
    return codes, keys


def _prints_as_hashed(uniques) -> bool:
    """Whether values that hash as one also print as one: uniques of one
    Python type among strings and integers, or floats without a zero."""
    types = {type(u) for u in uniques}
    if len(types) > 1:  # 1, 1.0 and True hash as one value
        return False
    t = types.pop() if types else str
    if issubclass(t, (str, int, np.integer, np.bool_)):
        return True
    if issubclass(t, (float, np.floating)):  # -0.0 == 0.0
        return not any(u == 0 for u in uniques)
    return False


def _lookup(keys: List[str], labels: List[str]) -> np.ndarray:
    """Each key's float64 index in ``labels``; ``len(labels)`` for a key
    the vocabulary does not hold."""
    index = {l: float(i) for i, l in enumerate(labels)}
    unseen = float(len(labels))
    return np.array([index.get(k, unseen) for k in keys], np.float64)


def _ordered(counts: Dict[str, int], order: str) -> List[str]:
    if order == "frequencyDesc":
        return [l for l, _ in sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))]
    if order == "frequencyAsc":
        return [l for l, _ in sorted(counts.items(), key=lambda kv: (kv[1], kv[0]))]
    if order == "alphabetDesc":
        return sorted(counts, reverse=True)
    if order == "alphabetAsc":
        return sorted(counts)
    raise ValueError(f"unknown stringOrderType {order!r}")


def _learn_labels(values: np.ndarray, order: str):
    """``(labels, codes, lut)``: the column's vocabulary under ``order``
    from one hash pass, with ``lut[codes]`` each row's float64 index."""
    codes, keys = _hash_labels(values)
    counts: Dict[str, int] = {}
    for k, c in zip(keys, np.bincount(codes, minlength=len(keys)).tolist()):
        counts[k] = counts.get(k, 0) + c
    labels = _ordered(counts, order)
    return labels, codes, _lookup(keys, labels)


class _StringIndexerParams:
    inputCol = Param("input string column", default="label")
    outputCol = Param("output index column", default="labelIndex")
    inputCols = Param(
        "multi-column mode (Spark 3.0): input columns", default=None
    )
    outputCols = Param(
        "multi-column mode: output columns (same length)", default=None
    )
    stringOrderType = Param(
        "label ordering: frequencyDesc | frequencyAsc | alphabetDesc | alphabetAsc",
        default="frequencyDesc",
        validator=validators.one_of(
            "frequencyDesc", "frequencyAsc", "alphabetDesc", "alphabetAsc"
        ),
    )
    handleInvalid = Param(
        "unseen labels at transform: error | skip | keep",
        default="error",
        validator=validators.one_of("error", "skip", "keep"),
    )


def _resolve_cols(stage) -> tuple:
    """(ins, outs) for single- or multi-column mode (Spark 3.0: exactly
    one of inputCol/inputCols drives)."""
    multi_in = stage.getInputCols()
    if multi_in:
        outs = stage.getOutputCols()
        if not outs or len(outs) != len(multi_in):
            raise ValueError(
                "outputCols must be set and match inputCols in length"
            )
        return list(multi_in), list(outs)
    return [stage.getInputCol()], [stage.getOutputCol()]


def _index_values(values: np.ndarray, labels: List[str]) -> np.ndarray:
    """Vocab lookup over one hash pass: float64 indices, ``len(labels)``
    marking a value the vocabulary does not hold."""
    codes, keys = _hash_labels(values)
    return _lookup(keys, labels)[codes]


def _write_indices(frame: Frame, ins, outs, indexed, mode: str) -> Frame:
    """Apply ``handleInvalid`` and append the index columns; ``indexed``
    holds ``(out, n_labels)`` per input column."""
    results, bad_any = [], np.zeros(frame.num_rows, bool)
    for c, (out, n_labels) in zip(ins, indexed):
        bad = out == float(n_labels)
        if bad.any() and mode == "error":
            unseen = sorted({str(v) for v in np.asarray(frame[c])[bad]})
            raise ValueError(
                f"StringIndexer: unseen labels {unseen} in column "
                f"{c!r} (handleInvalid='error')"
            )
        results.append(out)
        bad_any |= bad
    if mode == "skip" and bad_any.any():
        # Spark drops the ROW if any indexed column is unseen
        keep = ~bad_any
        frame = frame.filter(keep)
        results = [r[keep] for r in results]
    for name, out in zip(outs, results):
        frame = frame.with_column(name, out)
    return frame


class StringIndexer(_StringIndexerParams, Estimator):
    def _fit(self, frame: Frame) -> "StringIndexerModel":
        ins, _ = _resolve_cols(self)
        order = self.getStringOrderType()
        labels_array = []
        for c in ins:
            labels_array.append(_learn_labels(frame[c], order)[0])
            inc(_PASSES, site="fit")
        return self._model(labels_array)

    def fit_transform(self, frame: Frame):
        """Fit and index ``frame`` from one hash pass a column.  A pipeline
        fit would otherwise hash its label column twice, once to learn the
        vocabulary and once to index the very rows it learned it from; the
        result is the one ``fit(frame).transform(frame)`` gives."""
        ins, outs = _resolve_cols(self)
        order = self.getStringOrderType()
        labels_array, indexed = [], []
        for c in ins:
            labels, codes, lut = _learn_labels(frame[c], order)
            inc(_PASSES, site="fit_transform")
            labels_array.append(labels)
            indexed.append((lut[codes], len(labels)))
        model = self._model(labels_array)
        return model, _write_indices(
            frame, ins, outs, indexed, self.getHandleInvalid()
        )

    def _model(self, labels_array) -> "StringIndexerModel":
        model = StringIndexerModel(labelsArray=labels_array)
        model.setParams(**self.paramValues())
        return model


class StringIndexerModel(_StringIndexerParams, Model):
    def __init__(self, labels: List[str] = None, labelsArray=None, **kwargs):
        super().__init__(**kwargs)
        if labelsArray is None:
            labelsArray = [list(labels or [])]
        self.labelsArray = [list(ls) for ls in labelsArray]

    @property
    def labels(self) -> List[str]:
        """Single-column accessor (the Spark attribute); multi-column
        models expose ``labelsArray``."""
        return self.labelsArray[0]

    def _save_extra(self):
        return {"labelsArray": self.labelsArray}, {}

    @classmethod
    def _load_from(cls, params, extra, arrays):
        if "labelsArray" in extra:
            m = cls(labelsArray=extra["labelsArray"])
        else:  # models persisted before multi-column support
            m = cls(labels=extra["labels"])
        m.setParams(**params)
        return m

    def transform(self, frame: Frame) -> Frame:
        ins, outs = _resolve_cols(self)
        if len(ins) != len(self.labelsArray):
            raise ValueError(
                f"model was fitted on {len(self.labelsArray)} columns, "
                f"transform asked for {len(ins)}"
            )
        indexed = []
        for c, labels in zip(ins, self.labelsArray):
            indexed.append((_index_values(frame[c], labels), len(labels)))
            inc(_PASSES, site="transform")
        return _write_indices(
            frame, ins, outs, indexed, self.getHandleInvalid()
        )


class IndexToString(Transformer):
    """Inverse map: index column -> label strings (Spark ``IndexToString``)."""

    inputCol = Param("input index column", default="prediction")
    outputCol = Param("output string column", default="predictedLabel")
    labels = Param("label vocabulary, index order")

    def transform(self, frame: Frame) -> Frame:
        labels = self.getLabels()
        idx = frame[self.getInputCol()].astype(np.int64)
        if (idx < 0).any() or (idx >= len(labels)).any():
            raise ValueError("IndexToString: index out of label range")
        out = np.asarray(labels, dtype=object)[idx]
        return frame.with_column(self.getOutputCol(), out)
