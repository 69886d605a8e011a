"""VectorAssembler — concatenate numeric columns into one feature vector.

Behavioral spec: SURVEY.md §2.2 (upstream ``ml/feature/VectorAssembler.scala``
[U]): dense concatenation in declared column order; ``handleInvalid`` is
``error`` (raise on NaN), ``skip`` (drop rows), or ``keep`` (pass NaN
through).  Output is a ``(N, D)`` float32 vector column — this framework's
``VectorUDT`` analog (sntc_tpu.core.frame).
"""

from __future__ import annotations

from collections import OrderedDict
from typing import List

import numpy as np

from sntc_tpu.core.base import Transformer
from sntc_tpu.core.frame import Frame
from sntc_tpu.core.params import Param, validators
from sntc_tpu.feature.stack import pool_workers, stack_rows
from sntc_tpu.obs import inc, module_of, span

_MODULE = module_of(__name__)

# assembly memo, keyed on the IDENTITY of the input column arrays (Frames
# are immutable and share column arrays across with_column/rename, so the
# same columns ⇒ the same stack).  Re-fitting on one dataset then reuses
# one X object, which keeps the downstream device-residency cache
# (sntc_tpu.parallel.collectives) hot — without this, every fit restacks
# 62 MB AND re-uploads it.  Input columns are held by WEAK reference: a
# dead column invalidates (and sweeps) the entry, so dropping the dataset
# frees the memo too, and a recycled id can never false-hit.  Shares the
# ``SNTC_DEVICE_CACHE_MB=0`` kill switch with the device cache.
_ASSEMBLE_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_ASSEMBLE_CACHE_MAX = 4
# memoize only fit-scale stacks: serving micro-batches (a fresh small
# frame per batch) would churn insert+sweep on the [B:11] hot path for
# entries that can never hit again
_ASSEMBLE_MEMO_MIN_BYTES = 8 << 20


class VectorAssembler(Transformer):
    inputCols = Param("input column names, concatenated in order")
    outputCol = Param("output vector column", default="features")
    handleInvalid = Param(
        "how to handle NaN/Inf rows: error | skip | keep",
        default="error",
        validator=validators.one_of("error", "skip", "keep"),
    )

    def transform(self, frame: Frame) -> Frame:
        import weakref

        from sntc_tpu.parallel.collectives import _device_cache_max_bytes

        names: List[str] = self.getInputCols()
        cols = [frame[name] for name in names]
        mode = self.getHandleInvalid()

        widths = [1 if c.ndim == 1 else c.shape[1] for c in cols]
        memo_on = (
            _device_cache_max_bytes() > 0
            and frame.num_rows * sum(widths) * 4 >= _ASSEMBLE_MEMO_MIN_BYTES
        )
        if _ASSEMBLE_CACHE:
            # sweep entries whose input columns were garbage-collected
            for k in [
                k for k, e in _ASSEMBLE_CACHE.items()
                if any(r() is None for r in e[0])
            ]:
                del _ASSEMBLE_CACHE[k]
        key = (tuple(id(c) for c in cols), mode)
        hit = _ASSEMBLE_CACHE.get(key) if memo_on else None
        if hit is not None and all(
            r() is c for r, c in zip(hit[0], cols)
        ):
            _ASSEMBLE_CACHE.move_to_end(key)
            X, invalid = hit[1], hit[2]
        else:
            one_d = bool(cols) and all(c.ndim == 1 for c in cols)
            # a fit-scale stack is shared out among a pool, a serving
            # micro-batch [B:11] is one call: feature/stack.py
            workers = pool_workers(
                len(cols), frame.num_rows * len(cols) * 4
            ) if one_d else 1
            unchecked = mode != "keep"
            with span("assemble.stack", columns=len(cols), workers=workers,
                      module=_MODULE):
                if one_d:
                    # all-1-D-columns fast path: a C-level stack+cast into
                    # the feature-major base (4× the per-column assign
                    # loop), each column checked for NaN/Inf by the copy
                    # that brought it in; the transposed view
                    # multiplies/converts downstream at full speed, so no
                    # contiguity copy.  (N, 1) 2-D columns must take the
                    # assign loop: np.array would stack them to 3-D
                    base, finite = stack_rows(
                        cols, np.float32, finite=unchecked, workers=workers
                    )
                    unchecked = unchecked and not finite.all()
                    X = base.T
                else:
                    # single allocation, cast-on-assign — no per-column
                    # intermediate copies
                    X = np.empty((frame.num_rows, sum(widths)), np.float32)
                    off = 0
                    for col, w in zip(cols, widths):
                        if col.ndim == 1:
                            X[:, off] = col
                        else:
                            X[:, off : off + w] = col
                        off += w
            inc("sntc_feature_copy_bytes_total", X.nbytes,
                site="assemble.stack")
            if workers > 1:
                inc("sntc_feature_pooled_copies_total", site="assemble.stack")

            invalid = None
            if unchecked:  # some column holds a NaN/Inf: which rows
                with span("assemble.finite_check", module=_MODULE):
                    bad = ~np.isfinite(X).all(axis=1)
                if bad.any():
                    if mode == "error":
                        raise ValueError(
                            f"VectorAssembler: {int(bad.sum())} rows contain "
                            "NaN/Inf (handleInvalid='error'); clean the data "
                            "or use handleInvalid='skip'"
                        )
                    invalid = bad
            if memo_on:
                try:
                    refs = tuple(weakref.ref(c) for c in cols)
                except TypeError:
                    refs = None  # non-weakref-able column type
                if refs is not None:
                    _ASSEMBLE_CACHE[key] = (refs, X, invalid)
                    while len(_ASSEMBLE_CACHE) > _ASSEMBLE_CACHE_MAX or (
                        len(_ASSEMBLE_CACHE) > 1
                        and sum(
                            e[1].nbytes for e in _ASSEMBLE_CACHE.values()
                        )
                        > (2 << 30)
                    ):
                        _ASSEMBLE_CACHE.popitem(last=False)

        if invalid is not None:  # skip mode with rows to drop
            frame = frame.filter(~invalid)
            X = X[~invalid]
        return frame.with_column(self.getOutputCol(), X)
