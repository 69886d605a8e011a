"""Binned contingency/histogram kernels — the ``DTStatsAggregator`` analog.

Spark accumulates per-partition (feature, bin, class) sufficient statistics in
mutable JVM arrays and shuffles them to the driver (SURVEY.md §3.2).  Here the
whole statistic is one dense ``segment_sum`` per shard, ``psum``-reduced over
the mesh by the caller (sntc_tpu.parallel.collectives) — no shuffle, no
driver hop.  The same kernel serves ChiSqSelector (contingency [B:9]) and the
tree growers (per-node histograms, sntc_tpu/models/tree).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("n_bins", "n_classes"))
def binned_contingency(
    binned: jnp.ndarray,  # [N, F] int32 bin ids
    y: jnp.ndarray,  # [N] int32 class ids
    w: jnp.ndarray,  # [N] f32 row weights (0 on padding)
    *,
    n_bins: int,
    n_classes: int,
) -> jnp.ndarray:
    """Weighted (feature, bin, class) counts, shape ``[F, B, C]`` f32."""
    n, f = binned.shape
    feat_ids = jnp.arange(f, dtype=jnp.int32)[None, :]
    flat_ids = (feat_ids * n_bins + binned) * n_classes + y[:, None]
    weights = jnp.broadcast_to(w[:, None], (n, f))
    out = jax.ops.segment_sum(
        weights.ravel(),
        flat_ids.ravel(),
        num_segments=f * n_bins * n_classes,
    )
    return out.reshape(f, n_bins, n_classes)


def binned_contingency_onehot(
    binned: jnp.ndarray,  # [N, F] int32 bin ids
    y: jnp.ndarray,  # [N] int32 class ids
    w: jnp.ndarray,  # [N] f32 row weights (0 on padding)
    *,
    n_bins: int,
    n_classes: int,
) -> jnp.ndarray:
    """MXU path for :func:`binned_contingency` — the pallas level-histogram
    kernel with a single "node" (the segment_sum form scatter-adds
    N×F elements, which serialize on TPU)."""
    from sntc_tpu.ops.pallas_histogram import level_histogram_pallas

    # class indicators with the rows along lanes, a whole sublane tile of
    # them (the classes past ``n_classes`` match no label); the kernel
    # multiplies the row weight in on its own tile.  One "tree", one node
    yoh_t = jax.nn.one_hot(
        y, -(-n_classes // 8) * 8, dtype=jnp.float32, axis=0
    )
    node0 = jnp.zeros((1, y.shape[0]), jnp.int32)
    return level_histogram_pallas(
        binned.T, node0, yoh_t, w[None, :],
        n_nodes=1, n_bins=n_bins,
    )[0, ..., :n_classes]  # [F, B, C]


def chi_square(observed: np.ndarray) -> tuple:
    """Pearson χ² per feature from contingency ``[F, B, C]``.

    Returns ``(stats [F], p_values [F], dof [F])``.  Semantics follow Spark's
    ``ChiSqTest`` on categorical data (SURVEY.md §2.2): expected counts from
    row/column marginals, dof = (#nonempty bins - 1) * (#nonempty classes - 1).
    Host-side — the contingency is tiny (78×32×15).
    """
    from scipy.stats import chi2 as chi2_dist

    observed = np.asarray(observed, dtype=np.float64)
    f = observed.shape[0]
    stats = np.zeros(f)
    dofs = np.zeros(f, dtype=np.int64)
    for j in range(f):
        table = observed[j]
        table = table[table.sum(axis=1) > 0][:, table.sum(axis=0) > 0]
        if table.size == 0 or 1 in table.shape:
            stats[j], dofs[j] = 0.0, 0
            continue
        total = table.sum()
        expected = np.outer(table.sum(axis=1), table.sum(axis=0)) / total
        stats[j] = ((table - expected) ** 2 / expected).sum()
        dofs[j] = (table.shape[0] - 1) * (table.shape[1] - 1)
    p_values = np.where(dofs > 0, chi2_dist.sf(stats, np.maximum(dofs, 1)), 1.0)
    return stats, p_values, dofs
