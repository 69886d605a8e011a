"""Shared univariate feature-selection modes.

One implementation of Spark's five ``selectorType``/``selectionMode``
semantics (upstream ``ml/feature/{ChiSqSelector,UnivariateFeatureSelector}.
scala`` [U]) used by both selectors: rank by p-value ascending (stat
descending, index ascending on ties) and keep

  * ``numTopFeatures`` — the best k,
  * ``percentile``     — the best ``ceil-free int(F * fraction)`` (min 1),
  * ``fpr``            — every feature with ``p < threshold``,
  * ``fdr``            — Benjamini-Hochberg step-up at ``threshold``,
  * ``fwe``            — Bonferroni: ``p < threshold / F``.

And the package's one column-take, :func:`take_columns`, which the
selector models and ``VectorSlicer`` apply their selection with.
"""

from __future__ import annotations

from typing import List

import numpy as np

from sntc_tpu.feature.stack import pool_workers, stack_rows
from sntc_tpu.obs import inc, module_of, span

_MODULE = module_of(__name__)


def take_columns(X, idx) -> np.ndarray:
    """``X[:, idx]`` as a fresh host matrix, copied the way ``X`` lies.

    The assembler hands on a feature-major matrix (a ``[F, N]`` base seen
    as its ``(N, F)`` transpose): its columns are whole contiguous rows of
    the base, so taking them is ``len(idx)`` memcpys and the result stays
    feature-major, where a fancy index into row-major misses a cache line
    on every element; at fit scale a pool of workers makes them
    (``feature/stack.py``).  A row-major matrix takes ``np.take`` along axis 1;
    anything else (a strided slice, a device-resident column) the plain
    fancy index.  Same values, dtype and shape in every branch — only the
    strides of the result follow the input's.  The branch taken is the
    ``layout`` of the ``select.take`` span and of the bytes counted into
    ``sntc_feature_copy_bytes_total``.
    """
    idx = np.asarray(idx, np.intp)
    if not (isinstance(X, np.ndarray) and X.ndim == 2):
        layout = "generic"
    elif X.flags.c_contiguous:
        layout = "columns"
    elif X.flags.f_contiguous:
        layout = "base_rows"
    else:
        layout = "generic"
    workers = pool_workers(
        len(idx), len(idx) * X.shape[0] * X.itemsize
    ) if layout == "base_rows" else 1
    with span("select.take", layout=layout, module=_MODULE):
        if workers > 1:
            out = stack_rows([X.T[i] for i in idx], X.dtype,
                             workers=workers)[0].T
        elif layout == "base_rows":
            out = np.take(X.T, idx, axis=0).T
        elif layout == "columns":
            out = np.take(X, idx, axis=1)
        else:
            out = np.ascontiguousarray(X[:, idx])
    inc("sntc_feature_copy_bytes_total", out.nbytes,
        site="select.take", layout=layout)
    if workers > 1:
        inc("sntc_feature_pooled_copies_total", site="select.take")
    return out


def select_features_by_mode(
    stats: np.ndarray,
    p_values: np.ndarray,
    mode: str,
    threshold,
    n_features: int,
) -> List[int]:
    """Sorted selected feature indices; ``threshold`` is the mode's knob
    (k / fraction / p-cutoff)."""
    order = np.lexsort((np.arange(len(stats)), -stats, p_values))
    if mode == "numTopFeatures":
        chosen = order[: min(int(threshold), n_features)]
    elif mode == "percentile":
        chosen = order[: max(1, int(n_features * float(threshold)))]
    elif mode == "fpr":
        chosen = np.flatnonzero(p_values < float(threshold))
    elif mode == "fdr":
        # Benjamini-Hochberg step-up: largest k with p_(k) <= k/F * fdr,
        # then every feature at or below that cutoff
        sorted_p = p_values[order]
        cuts = (np.arange(1, n_features + 1) / n_features) * float(threshold)
        below = np.flatnonzero(sorted_p <= cuts)
        chosen = order[: below[-1] + 1] if below.size else order[:0]
    elif mode == "fwe":
        chosen = np.flatnonzero(p_values < float(threshold) / n_features)
    else:
        raise ValueError(f"unknown selection mode {mode!r}")
    return sorted(int(i) for i in chosen)
