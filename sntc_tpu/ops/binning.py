"""Quantile binning — the ``findSplits`` analog (SURVEY.md §3.2).

Spark's tree path bins continuous features once into small integer bin ids
(``TreePoint.convertToTreePoint`` after ``findSplits`` quantile sampling [U])
so every later pass is integer histogramming.  We keep that design because it
is exactly what the TPU wants: the feature matrix becomes a device-resident
``int32 [N, F]`` tensor of bin ids (4 bytes a value: 1.27 GB for 4.06M×78)
and every histogram is a one-hot matmul or a ``segment_sum`` over it
(SURVEY.md §7.1 step 4).

Edge computation is sample-based like Spark's ``findSplits`` (which draws
``max(maxBins², 10000)`` rows); measured on the bench workload, macro-F1 is
flat from 200k samples down to 10k, so the default sample scales with the
bin count.  Host (numpy) inputs compute edges on host; device-resident
columns (``jax.Array`` — e.g. handed down by a fitted scaler, or the 2.8M
full-scale matrix already in HBM) compute them ON DEVICE with a jitted
``jnp.quantile`` — no device→host round trip for the feature matrix.
``bin_features`` is jitted and runs on device: a compare-and-count over the
edges, one elementwise pass with no gather (a per-value binary search lowers
to serial gathers on the TPU).  Static output shape ``[F, max_bins - 1]``;
duplicate edges from low-cardinality features are harmless (empty bins).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _default_sample_rows(max_bins: int) -> int:
    # Spark findSplits: max(maxBins * maxBins, 10000); we add headroom
    return max(10_000, 4 * max_bins * max_bins)


@partial(jax.jit, static_argnames=("max_bins", "sample_rows"))
def _edges_device(
    X: jnp.ndarray, seed: jnp.ndarray, *, max_bins: int, sample_rows: int
) -> jnp.ndarray:
    qs = jnp.linspace(0.0, 1.0, max_bins + 1)[1:-1]
    n = X.shape[0]
    if sample_rows < n:
        # seed-keyed uniform sample without replacement, matching the
        # host path's semantics: a strided X[::k] sample would bias the
        # edges on device matrices with periodic/sorted row structure
        # (flow data ordered by time or label)
        idx = jax.random.choice(
            jax.random.PRNGKey(seed), n, shape=(sample_rows,), replace=False
        )
        sample = X[idx]
    else:
        sample = X
    return jnp.quantile(sample.astype(jnp.float32), qs, axis=0).T


def quantile_bin_edges(
    X,
    max_bins: int = 32,
    sample_rows: Optional[int] = None,
    seed: int = 0,
):
    """Per-feature quantile split thresholds, shape ``[F, max_bins - 1]``.

    Returns an ndarray matching the input's residency: numpy in → numpy
    edges (host quantile of a ``seed``-driven random row sample);
    ``jax.Array`` in → device edges from a ``seed``-keyed
    ``jax.random.choice`` row sample (without replacement) — the feature
    matrix never leaves the device.  With ``sample_rows >= n`` both paths
    use every row and agree to float tolerance (tests/test_trees.py
    parity test).
    """
    n, f = X.shape
    if sample_rows is None:
        sample_rows = _default_sample_rows(max_bins)
    if isinstance(X, jax.Array):
        return _edges_device(
            X, jnp.uint32(seed & 0xFFFFFFFF),
            max_bins=max_bins, sample_rows=min(int(sample_rows), n),
        )
    if n > sample_rows:
        idx = np.random.default_rng(seed).choice(n, size=sample_rows, replace=False)
        sample = X[idx]
    else:
        sample = X
    qs = np.linspace(0.0, 1.0, max_bins + 1)[1:-1]
    edges = np.quantile(sample, qs, axis=0).T.astype(np.float32)  # [F, B-1]
    return np.ascontiguousarray(edges)


@jax.jit
def bin_features(X: jnp.ndarray, edges: jnp.ndarray) -> jnp.ndarray:
    """Map ``X [N, F]`` to bin ids ``[N, F]`` (int32 in [0, B-1]) given
    ``edges [F, B-1]``: ``bin = #edges <= x`` (right-closed, Spark-style).

    Equal, value for value, to ``searchsorted(edges[f], x, side="right")``
    on sorted edges — ties go right, NaN sorts last (bin ``B-1``) — but
    counted with one compare per edge: the loop unrolls into a single
    elementwise fusion that reads ``X`` once and holds no ``[N, F, B]``
    temporary.
    """
    is_nan = jnp.isnan(X)
    bins = jnp.zeros(X.shape, jnp.int32)
    for j in range(edges.shape[1]):
        bins = bins + ((X >= edges[:, j]) | is_nan).astype(jnp.int32)
    return bins
