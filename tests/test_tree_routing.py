"""Row routing of the level-wise grower (``grower._route_rows``).

The routing is compare-and-select work over the level's decision tables;
the law it must keep is the per-row table lookup it replaced, kept here as
the ``take_along_axis`` oracle.  The estimators that share the grower are
held to the arrays the gather routing gave (``testdata/tree_routing_expected
.npz``, written on the CPU from the commit before the change by running
this file's ``_FITS`` against that checkout).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sntc_tpu.core.frame import Frame
from sntc_tpu.models import (
    DecisionTreeClassifier,
    GBTClassifier,
    OneVsRest,
    RandomForestClassifier,
)
from sntc_tpu.models.tree import grower

EXPECTED = os.path.join(
    os.path.dirname(__file__), "testdata", "tree_routing_expected.npz"
)


def _oracle(binned, node_idx, best_feat, best_bin, do_split):
    """The per-row gathers the grower routed by before."""
    idx = jnp.where(node_idx >= 0, node_idx, 0)
    splits = jnp.take_along_axis(do_split, idx, axis=1)
    feats = jnp.take_along_axis(best_feat, idx, axis=1)
    bins_thr = jnp.take_along_axis(best_bin, idx, axis=1)
    row_bins = jax.vmap(
        lambda f_t: jnp.take_along_axis(
            binned, f_t.clip(0)[:, None], axis=1
        )[:, 0]
    )(feats)
    child = 2 * idx + (row_bins > bins_thr).astype(jnp.int32)
    return jnp.where((node_idx >= 0) & splits, child, -1).astype(jnp.int32)


@pytest.mark.parametrize("n_nodes", [1, 2, 8, 64, 256])
@pytest.mark.parametrize("F", [40, 78])
@pytest.mark.parametrize("T", [1, 20])
def test_route_rows_matches_gather_oracle(T, F, n_nodes):
    """Element for element, with dead rows, nodes that do not split (and
    carry a negative ``best_feat``), N not a multiple of 128, from the
    root level to a deep one."""
    n, n_bins = 1003, 32
    rng = np.random.default_rng(1000 * T + 10 * F + n_nodes)
    binned = rng.integers(0, n_bins, size=(n, F)).astype(np.int32)
    node_idx = rng.integers(-1, n_nodes, size=(T, n)).astype(np.int32)
    do_split = rng.random((T, n_nodes)) < 0.75
    do_split[:, -1] = False  # a node that does not split, in every tree
    feat = rng.integers(0, F, size=(T, n_nodes)).astype(np.int32)
    feat[:, 0] = F - 1  # the last feature row is reachable
    best_feat = np.where(do_split, feat, -1 - feat % 3).astype(np.int32)
    best_bin = rng.integers(0, n_bins - 1, size=(T, n_nodes)).astype(np.int32)

    got = jax.jit(grower._route_rows)(
        jnp.asarray(binned.T), jnp.asarray(node_idx), jnp.asarray(best_feat),
        jnp.asarray(best_bin), jnp.asarray(do_split),
    )
    want = _oracle(
        jnp.asarray(binned), jnp.asarray(node_idx), jnp.asarray(best_feat),
        jnp.asarray(best_bin), jnp.asarray(do_split),
    )
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == np.int32 and got.shape == (T, n)
    np.testing.assert_array_equal(got, want)
    # the cases mean something: dead rows stay dead, rows of a node that
    # does not split die, live rows land on both children
    assert (got[node_idx < 0] == -1).all()
    assert (got[node_idx == n_nodes - 1] == -1).all()
    if n_nodes >= 8:
        live = got[got >= 0]
        assert (live % 2 == 0).any() and (live % 2 == 1).any()


@pytest.mark.parametrize("n_nodes", [8, 256])
def test_route_rows_lowers_without_gather(n_nodes):
    """The point of the change: no gather, no dynamic per-row indexing and
    no loop in what the compiler is handed, at a shallow and a deep level."""
    txt = jax.jit(grower._route_rows).lower(
        jax.ShapeDtypeStruct((40, 4096), jnp.int32),
        jax.ShapeDtypeStruct((20, 4096), jnp.int32),
        jax.ShapeDtypeStruct((20, n_nodes), jnp.int32),
        jax.ShapeDtypeStruct((20, n_nodes), jnp.int32),
        jax.ShapeDtypeStruct((20, n_nodes), jnp.bool_),
    ).as_text()
    for op in ("gather", "scatter", "while", "dynamic_slice"):
        assert op not in txt, op


def _data(n, k, d, seed):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)) * 2.0
    y = rng.integers(0, k, size=n)
    X = (centers[y] + rng.normal(size=(n, d))).astype(np.float32)
    return Frame({"features": X, "label": y.astype(np.float64)})


def _forest_arrays(forest):
    return {
        "feature": forest.feature, "threshold": forest.threshold,
        "leaf_stats": forest.leaf_stats, "gain": forest.gain,
        "count": forest.count,
    }


def _fit_forest(mesh):
    m = RandomForestClassifier(
        mesh=mesh, numTrees=7, maxDepth=5, maxBins=32, seed=29
    ).fit(_data(3001, 5, 12, 1))
    return _forest_arrays(m.forest)


def _fit_decision_tree(mesh):
    m = DecisionTreeClassifier(
        mesh=mesh, maxDepth=4, maxBins=16, seed=29
    ).fit(_data(1501, 4, 9, 2))
    return _forest_arrays(m.forest)


def _fit_deep_tree(mesh):
    """Depth 9: routed levels of up to 128 nodes."""
    m = DecisionTreeClassifier(
        mesh=mesh, maxDepth=9, maxBins=32, minInstancesPerNode=1, seed=29
    ).fit(_data(6001, 6, 8, 3))
    return _forest_arrays(m.forest)


def _fit_gbt_binary(mesh):
    m = GBTClassifier(
        mesh=mesh, maxIter=4, maxDepth=3, stepSize=0.2, seed=29
    ).fit(_data(1201, 2, 6, 4))
    out = _forest_arrays(m.forest)
    out["tree_weights"] = np.asarray(m.treeWeights)
    return out


def _fit_gbt_ovr(mesh):
    """OneVsRest over a GBT dispatches to the vectorised fit: the class
    axis rides the grower's tree axis with per-tree row stats."""
    clf = GBTClassifier(mesh=mesh, maxIter=3, maxDepth=3, stepSize=0.2, seed=29)
    ovr = OneVsRest(classifier=clf).fit(_data(1201, 3, 5, 5))
    out = {}
    for c, m in enumerate(ovr.models):
        for k, v in _forest_arrays(m.forest).items():
            out[f"c{c}.{k}"] = v
    return out


_FITS = {
    "forest": _fit_forest,
    "decision_tree": _fit_decision_tree,
    "deep_tree": _fit_deep_tree,
    "gbt_binary": _fit_gbt_binary,
    "gbt_ovr": _fit_gbt_ovr,
}


@pytest.mark.parametrize("name", sorted(_FITS))
def test_fits_equal_the_parents_arrays(mesh8, name):
    """Seeded fits of every estimator family that shares ``_level_core``
    give the arrays the gather routing gave, bit for bit."""
    got = _FITS[name](mesh8)
    with np.load(EXPECTED) as want:
        keys = [k for k in want.files if k.startswith(name + "/")]
        assert sorted(keys) == sorted(f"{name}/{k}" for k in got)
        for k, v in got.items():
            np.testing.assert_array_equal(
                np.asarray(v), want[f"{name}/{k}"], err_msg=f"{name}/{k}"
            )
    if name == "deep_tree":  # the 128-node level really routed rows
        internal = got["feature"][0, (1 << 7) - 1:(1 << 8) - 1] >= 0
        assert internal.sum() > 0


# --------------------------------------------------------------------------
# the walk on raw floats (``grower.forest_leaf_stats``)
# --------------------------------------------------------------------------


def _walk_oracle(X, feature, threshold, leaf_stats, max_depth):
    """The per-row gathers the walk was before: three ``take_along_axis``
    a level and the leaf fetch."""
    T, N = feature.shape[0], X.shape[0]
    node = jnp.zeros((T, N), jnp.int32)
    for _ in range(max_depth):
        f = jnp.take_along_axis(feature, node, axis=1)
        is_internal = f >= 0
        fc = jnp.where(is_internal, f, 0)
        xv = jax.vmap(
            lambda f_t: jnp.take_along_axis(X, f_t[:, None], axis=1)[:, 0]
        )(fc)
        thr = jnp.take_along_axis(threshold, node, axis=1)
        child = 2 * node + 1 + (xv >= thr).astype(jnp.int32)
        node = jnp.where(is_internal, child, node)
    return jax.vmap(lambda ls_t, n_t: ls_t[n_t])(leaf_stats, node)


def _random_heap(rng, T, depth, F, S, X):
    """Dense heaps as the grower writes them: an internal node's children
    exist, a leaf's subtree is absent (-2); some subtrees die early; every
    threshold is a value some row holds, so rows sit exactly on it."""
    H = (1 << (depth + 1)) - 1
    feature = np.full((T, H), -2, np.int32)
    threshold = np.zeros((T, H), np.float32)
    feature[:, 0] = -1
    for h in range((1 << depth) - 1):  # slots that have children
        split = (feature[:, h] == -1) & (rng.random(T) < 0.8)
        f = rng.integers(0, F, size=T)
        feature[:, h] = np.where(split, f, feature[:, h])
        threshold[:, h] = np.where(
            split, X[rng.integers(0, X.shape[0], size=T), f], 0.0
        )
        for child in (2 * h + 1, 2 * h + 2):
            feature[:, child] = np.where(split, -1, feature[:, child])
    feature[0, 0] = max(feature[0, 0], 0)  # one tree that surely splits
    leaf_stats = rng.normal(size=(T, H, S)).astype(np.float32)
    leaf_stats[..., 0] = rng.integers(1, 50, size=(T, H))
    return feature, threshold, leaf_stats


@pytest.mark.parametrize("S", [3, 15])
@pytest.mark.parametrize("T", [1, 15])
@pytest.mark.parametrize("depth", [1, 2, 3, 4, 5, 6])
def test_walk_matches_gather_oracle(depth, T, S):
    """Integers equal and floats bit-equal, with values exactly on a
    threshold, NaN features (which go left), dead subtrees and N not a
    multiple of 128; the boosting callers' leaf value is the table's own
    ``sum / max(count, 1e-12)``."""
    n, F = 1003, 11
    rng = np.random.default_rng(100 * depth + 10 * T + S)
    X = rng.integers(-3, 4, size=(n, F)).astype(np.float32)  # many ties
    X[rng.random((n, F)) < 0.3] += np.float32(rng.random())
    feature, threshold, leaf_stats = _random_heap(rng, T, depth, F, S, X)
    with jax.debug_nans(False):
        X[5, :] = np.nan
        args = tuple(jnp.asarray(a) for a in
                     (X, feature, threshold, leaf_stats))
        want = np.asarray(_walk_oracle(*args, depth))
        got = np.asarray(grower.forest_leaf_stats(*args, max_depth=depth))
        value = np.asarray(grower.forest_leaf_stats(
            *args, max_depth=depth, value=True
        ))
    assert got.shape == (T, n, S) and got.dtype == np.float32
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        value, want[..., 1] / np.maximum(want[..., 0], np.float32(1e-12))
    )
    # the cases mean something: rows sit on thresholds and land on both
    # sides of the root
    on_thr = X[:, feature[0, 0]] == threshold[0, 0]
    assert on_thr.any() and not on_thr.all()
    assert len(np.unique(got[0, ~np.isnan(X[:, 0]), 1])) > 1  # both sides


@pytest.mark.parametrize("value", [False, True])
def test_walk_lowers_without_gather(value):
    """No gather, no dynamic per-row indexing and no loop in what the
    compiler is handed, and (for the boosting callers) no ``[T, N, S]``
    array."""
    n = 4099
    txt = grower.forest_leaf_stats.lower(
        jax.ShapeDtypeStruct((n, 78), jnp.float32),
        jax.ShapeDtypeStruct((15, 63), jnp.int32),
        jax.ShapeDtypeStruct((15, 63), jnp.float32),
        jax.ShapeDtypeStruct((15, 63, 3), jnp.float32),
        max_depth=5, value=value,
    ).as_text()
    for op in ("gather", "scatter", "while", "dynamic_slice"):
        assert op not in txt, op
    assert (f"15x{n}x3x" in txt) is (not value)
