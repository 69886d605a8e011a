"""Plain reference for the one-vs-rest boosted-trees configuration.

Friedman's TreeBoost for the logistic loss as Spark's ``GradientBoostedTrees``
runs it under ``OneVsRest`` (``ml/tree/impl/GradientBoostedTrees.scala``,
``mllib/tree/loss/LogLoss.scala``), in straightforward numpy / ``jax.numpy``
float32 with every product at ``Precision.HIGHEST`` and every statistic summed
over the row blocks in float64 on the host:

* class ``c`` of the label index gives the signed labels ``y = +1`` where the
  row's label is ``c``, else ``-1``;
* round 0 fits a variance-impurity regression tree to ``y`` itself, with tree
  weight 1; round ``m >= 1`` fits one to the pseudo-residuals
  ``r = 2 y / (1 + exp(2 y F))`` of the margin ``F`` so far, and ``F`` grows by
  ``stepSize`` times the row's leaf mean;
* a node's statistics are ``[sum w, sum w r, sum w r^2]`` (``w`` = 1), its
  impurity ``sum w r^2 - (sum w r)^2 / sum w``; a split is a (feature, bin
  edge) pair among the 32 quantile bins of all 78 features, chosen by the
  largest variance gain ``(imp(node) - imp(left) - imp(right)) / count``;
  ``minInstancesPerNode`` 1 (both children hold a row), ``minInfoGain`` 0 (a
  split needs a gain above 0); depth 5; a leaf predicts its mean residual.

Departures from Spark, each on purpose:

* float32 where Spark computes in double (the configuration states float32);
* bin edges are quantiles of one seeded 10,000-row sample (``reference.py``'s
  law) where Spark's ``findSplits`` draws its own sample and merges equal
  values: the configuration states the law, so both sides draw the same edges;
* ``x >= threshold`` goes right (the edge itself belongs to the right child)
  where Spark sends ``x <= threshold`` left: the same partition of the bins,
  named from the other side;
* the leaf keeps the plain mean residual, as Spark's ``GBTClassifier`` does
  (no Newton step on the leaves);
* no validation column, no subsampling (``subsamplingRate`` 1), every feature
  at every node (``featureSubsetStrategy`` "all"): Spark's defaults.

A row picks its node's entry of a small table by a one-hot product (exact:
one factor 1, the rest 0), and its feature value by a one-hot select.

Imports nothing from ``sntc_tpu``; from ``reference.py`` only the label
index, the assembly, the bin-edge law and its row-block helpers.

``matmul`` is the arithmetic of the statistics before they are summed:
``"f32"`` is the reference, ``"bf16"`` the control (each statistic rounded to
one bfloat16 term, what one bfloat16 pass of a histogram product would give),
which the comparison has to refuse.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import reference as ref

HI = jax.lax.Precision.HIGHEST


def _pick(table, idx):
    """``table[idx]`` of a small float32 ``table [H]`` for ``idx [R]``."""
    return jnp.dot(
        jax.nn.one_hot(idx, table.shape[0], dtype=jnp.float32), table,
        precision=HI,
    )


def _feature_value(X, f):
    """``X[row, f[row]]`` (``f`` clipped at 0 for rows that rest)."""
    cols = jnp.arange(X.shape[1], dtype=jnp.int32)[None, :]
    return jnp.sum(jnp.where(jnp.maximum(f, 0)[:, None] == cols, X, 0.0),
                   axis=1)


def leaf_means(leaf_stats):
    """A leaf's prediction: the mean residual ``sum w r / sum w``."""
    s = np.asarray(leaf_stats, np.float32)
    return s[..., 1] / np.maximum(s[..., 0], np.float32(1e-12))


@partial(jax.jit, static_argnames=("depth",))
def _block_margin(X, feature, threshold, value, weights, *, depth):
    """``F [R]`` of one row block: ``sum_j weights[j] * tree_j(x)``, added
    tree by tree in float32 from 0 (a weight of 0 leaves ``F`` as it was);
    ``feature`` / ``threshold`` / ``value`` are ``[M, H]`` dense heaps."""

    def one(tree):
        feat, thr, val = tree
        node = jnp.zeros(X.shape[0], jnp.int32)
        for _ in range(depth):
            f = _pick(feat.astype(jnp.float32), node).astype(jnp.int32)
            right = _feature_value(X, f) >= _pick(thr, node)
            node = jnp.where(f >= 0, 2 * node + 1 + right.astype(jnp.int32),
                             node)
        return _pick(val, node)

    vals = jax.lax.map(one, (feature, threshold, value))  # [M, R]
    F = jnp.zeros(X.shape[0], jnp.float32)
    for j in range(feature.shape[0]):
        F = F + weights[j] * vals[j]
    return F


@partial(jax.jit, static_argnames=("n_nodes", "n_bins", "first", "matmul"))
def _block_hist(binned, node, y, margin, *, n_nodes, n_bins, first, matmul):
    """``[n_nodes * 3, F * B]`` sums of ``[w, w r, w r^2]`` of one row block
    (``node < 0``: the row is not in the level); ``first`` is round 0, whose
    ``r`` is the signed label itself."""
    r = y if first else 2.0 * y / (1.0 + jnp.exp(2.0 * y * margin))
    w = (node >= 0).astype(jnp.float32)
    stats = jnp.stack([w, w * r, w * r * r], axis=1)  # [R, 3]
    if matmul == "bf16":
        stats = stats.astype(jnp.bfloat16).astype(jnp.float32)
    elif matmul != "f32":
        raise ValueError(f"unknown arithmetic {matmul!r}")
    a = jax.nn.one_hot(node, n_nodes, dtype=jnp.float32)
    a = (a[:, :, None] * stats[:, None, :]).reshape(a.shape[0], -1)
    b = jax.nn.one_hot(binned, n_bins, dtype=jnp.float32)
    return jnp.dot(a.T, b.reshape(b.shape[0], -1), precision=HI)


@jax.jit
def _block_route(node, X, feat, thr, split):
    """Children of the rows' nodes of one level: ``2 * node + (x[feature] >=
    threshold)`` where the node split, else -1 (the row rests in a leaf)."""
    idx = jnp.maximum(node, 0)
    f = _pick(feat.astype(jnp.float32), idx).astype(jnp.int32)
    right = _feature_value(X, f) >= _pick(thr, idx)
    splits = _pick(split.astype(jnp.float32), idx) > 0.5
    return jnp.where((node >= 0) & splits,
                     2 * idx + right.astype(jnp.int32), -1)


def _impurity_w(s):
    """``sum w r^2 - (sum w r)^2 / sum w`` of ``s [..., 3]``, float64."""
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(s[..., 0] > 0,
                        s[..., 2] - s[..., 1] ** 2 / s[..., 0], 0.0)


def split_gains(hist):
    """``(gains [nodes, F, B-1], valid, parent [nodes, 3], left)`` in float64
    from ``hist [nodes, 3, F, B]``: the variance gain of every (feature, bin
    edge), ``-inf`` where a child would hold no row."""
    cum = np.cumsum(hist, axis=3)
    parent = cum[:, :, 0, -1]
    left = np.moveaxis(cum[:, :, :, :-1], 1, -1)  # [nodes, F, B-1, 3]
    right = parent[:, None, None, :] - left
    gain = (
        _impurity_w(parent)[:, None, None] - _impurity_w(left)
        - _impurity_w(right)
    ) / np.maximum(parent[:, 0], 1e-12)[:, None, None]
    valid = (left[..., 0] >= 1.0) & (right[..., 0] >= 1.0)
    return np.where(valid, gain, -np.inf), valid, parent, left


class BoostData:
    """The feature matrix, its bins and the label indices on the device, in
    row blocks; margins and node ids are lists of per-block arrays."""

    def __init__(self, X, y, *, max_bins, seed):
        self.n, self.F = X.shape
        self.max_bins = max_bins
        self.edges = ref.quantile_edges(X, max_bins, seed)
        edges_d = jnp.asarray(self.edges)
        self.parts = []
        for lo, hi in ref._blocks(self.n):
            nb = hi - lo
            n_pad = ref.ROW_BLOCK if self.n > ref.ROW_BLOCK else nb
            Xb = jnp.asarray(ref._pad_rows(X[lo:hi], n_pad))
            self.parts.append((
                nb, Xb, ref._bin(Xb, edges_d),
                jnp.asarray(ref._pad_rows(y[lo:hi].astype(np.int32), n_pad)),
            ))

    def signed_labels(self, c):
        return [jnp.where(yb == c, 1.0, -1.0).astype(jnp.float32)
                for _, _, _, yb in self.parts]

    def margins(self, feature, threshold, leaf_stats, weights, depth):
        """Per-block ``F`` of the trees ``[M, H]`` under ``weights [M]``."""
        args = tuple(jnp.asarray(a) for a in (
            np.asarray(feature, np.int32), np.asarray(threshold, np.float32),
            leaf_means(leaf_stats), np.asarray(weights, np.float32),
        ))
        return [_block_margin(Xb, *args, depth=depth)
                for _, Xb, _, _ in self.parts]


def walk_tree(data: BoostData, y_parts, margin_parts, depth, *, first,
              given=None, matmul="f32"):
    """Grow one regression tree level by level on the residuals of
    ``margin_parts`` (``given is None``), or follow the splits of ``given =
    (feature [H], threshold [H], leaf_stats [H, 3])`` and judge them.
    Returns ``(tree, report)``: the tree as the same three arrays with this
    walk's own statistics in its leaves, and for a followed tree

    * ``split_gain_gap``: the widest variance gain by which a split it made
      lies under the best split of that node (a leaf that could have split
      counts with its best gain), times the node's rows, over the root's
      ``sum w r^2``: the share of the tree's sum of squares that the split
      fails to remove (see the adapter's notes on the scales);
    * ``leaf_count_gap``: the widest difference of a leaf's row count;
    * ``leaf_value_gap``: the widest difference of a leaf's mean residual,
      times the leaf's share of the tree's rows, over the root's root mean
      square residual: what the leaf's error moves the mean margin by;
    * ``tree_shape_mismatch``: slots that are a split, a leaf or absent on
      one side only, thresholds that are no edge of their feature, and
      splits of a node no edge divides;
    * ``min_leaf_rows``, ``gain_gap_rows``: the fewest rows in a leaf, and
      the rows of the node that set ``split_gain_gap`` (read, not compared).
    """
    B, F = data.max_bins, data.F
    H = (1 << (depth + 1)) - 1
    feature = np.full(H, -2, np.int32)
    threshold = np.zeros(H, np.float32)
    leaf = np.zeros((H, 3), np.float64)
    gain_gap, gap_rows, shape_bad = 0.0, 0.0, 0
    nodes = [jnp.where(jnp.arange(Xb.shape[0]) < nb, 0, -1).astype(jnp.int32)
             for nb, Xb, _, _ in data.parts]
    exists = np.array([True])
    for d in range(depth):
        n_nodes, off = 1 << d, (1 << d) - 1
        hist = np.zeros((n_nodes * 3, F * B), np.float64)
        for (_, _, bb, _), nd, yb, mb in zip(data.parts, nodes, y_parts,
                                             margin_parts):
            hist += np.asarray(_block_hist(
                bb, nd, yb, mb, n_nodes=n_nodes, n_bins=B, first=first,
                matmul=matmul,
            ), np.float64)
        gains, valid, parent, left = split_gains(
            hist.reshape(n_nodes, 3, F, B)
        )
        flat = gains.reshape(n_nodes, -1)
        best = flat.argmax(axis=1)
        best_gain = flat[np.arange(n_nodes), best]
        possible = exists & valid.reshape(n_nodes, -1).any(axis=1)
        can_split = possible & (best_gain > 0)
        bf, bb_ = best // (B - 1), best % (B - 1)
        if d == 0:
            root = parent[0]
        # a node's gain is per row of the node; over the tree's sum of squares
        scale = root[2] / np.maximum(parent[:, 0], 1e-12)
        if given is None:
            split = can_split
        else:
            g_feat, g_thr, _ = given
            split = exists & (g_feat[off:off + n_nodes] >= 0)
            for j in np.flatnonzero(exists):
                if not split[j]:
                    if can_split[j] and best_gain[j] / scale[j] > gain_gap:
                        gain_gap = float(best_gain[j] / scale[j])
                        gap_rows = float(parent[j, 0])
                    continue
                f_j = int(g_feat[off + j])
                hits = (np.flatnonzero(data.edges[f_j] == g_thr[off + j])
                        if f_j < F else np.zeros(0, np.int64))
                if hits.size == 0 or not possible[j]:
                    shape_bad += 1
                    split[j] = False
                    continue
                bf[j], bb_[j] = f_j, hits[0]
                got = gains[j, f_j, hits[0]]
                gap = (float((best_gain[j] - got) / scale[j])
                       if np.isfinite(got) else 1.0)
                if gap > gain_gap:
                    gain_gap, gap_rows = gap, float(parent[j, 0])
        lvl = slice(off, off + n_nodes)
        feature[lvl] = np.where(split, bf, np.where(exists, -1, -2))
        threshold[lvl] = np.where(split, data.edges[bf, bb_], 0.0)
        leaf[lvl] = np.where((exists & ~split)[:, None], parent, 0.0)
        child_exists = np.repeat(split, 2)
        l_stats = left[np.arange(n_nodes), bf, bb_]
        kids = np.stack([l_stats, parent - l_stats], axis=1).reshape(-1, 3)
        lvl2 = slice(off + n_nodes, off + 3 * n_nodes)
        feature[lvl2] = np.where(child_exists, -1, -2)
        leaf[lvl2] = np.where(child_exists[:, None], kids, 0.0)
        exists = child_exists
        if d < depth - 1:
            feat_d = jnp.asarray(np.where(split, bf, 0).astype(np.int32))
            thr_d = jnp.asarray(threshold[lvl])
            split_d = jnp.asarray(split)
            nodes = [_block_route(nd, Xb, feat_d, thr_d, split_d)
                     for nd, (_, Xb, _, _) in zip(nodes, data.parts)]
    report = None
    if given is not None:
        g_feat, _, g_leaf = given
        g_leaf = np.asarray(g_leaf, np.float64)
        shape_bad += int(np.sum((g_feat >= 0) != (feature >= 0))
                         + np.sum((g_feat == -1) != (feature == -1)))
        at = (feature == -1) | (g_feat == -1)
        count_gap = float(np.max(np.abs(g_leaf[:, 0] - leaf[:, 0])[at],
                                 initial=0.0))
        mine = feature == -1
        cnt = np.maximum(leaf[mine, 0], 1e-12)
        theirs = g_leaf[mine, 1] / np.maximum(g_leaf[mine, 0], 1e-12)
        value_gap = float(np.max(
            np.abs(theirs - leaf[mine, 1] / cnt) * (cnt / root[0])
            / np.sqrt(root[2] / root[0]), initial=0.0
        ))
        report = {
            "split_gain_gap": gain_gap,
            "leaf_count_gap": count_gap,
            "leaf_value_gap": value_gap,
            "tree_shape_mismatch": shape_bad,
            "min_leaf_rows": float(np.min(leaf[mine, 0], initial=np.inf)),
            "gain_gap_rows": gap_rows,
        }
    return (feature, threshold, leaf.astype(np.float32)), report
