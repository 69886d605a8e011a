"""``ops/binning.py:bin_features`` — the compare-and-count binning law.

The function has to give, value for value, what a per-feature
``searchsorted(edges[f], x, side="right")`` gives (ties go right, duplicate
edges are empty bins, ``-0.0 == 0.0``, NaN sorts last), and it has to lower
without a gather: on the TPU a per-value binary search becomes serial
``kCustom`` gathers that took 23 s of a 65 s forest fit (PERF.md §6, PR 27).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sntc_tpu.ops.binning import bin_features, quantile_bin_edges


def _searchsorted_bins(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """The law, column by column, on the host."""
    return np.stack(
        [np.searchsorted(edges[f], X[:, f], side="right")
         for f in range(X.shape[1])],
        axis=1,
    ).astype(np.int32)


def _case(kind: str, max_bins: int):
    """``(X [N, 5], edges [5, max_bins - 1])`` with the special values of
    ``kind`` planted in column 0 (columns 1-4 stay plain draws)."""
    rng = np.random.default_rng(max_bins)
    X = rng.normal(size=(600, 5)).astype(np.float32)
    edges = quantile_bin_edges(X, max_bins=max_bins)
    if kind == "on_edges":  # every value of the column sits on an edge
        X[:, 0] = edges[0][rng.integers(0, max_bins - 1, size=600)]
    elif kind == "duplicate_edges":  # a constant column: all edges equal
        X[:, 0] = 3.0
        edges = quantile_bin_edges(X, max_bins=max_bins)
        assert (edges[0] == 3.0).all()
        X[::3, 0] = 2.0  # below, on and above the one repeated edge
        X[1::3, 0] = 4.0
    elif kind == "pos_inf":
        X[::5, 0] = np.inf
    elif kind == "neg_inf":
        X[::5, 0] = -np.inf
    elif kind == "neg_zero":  # -0.0 against a 0.0 edge (and 0.0 against it)
        edges[0, (max_bins - 1) // 2] = 0.0
        edges[0] = np.sort(edges[0])
        X[::4, 0] = -0.0
        X[1::4, 0] = 0.0
    elif kind == "nan":
        X[::5, 0] = np.nan
    else:
        assert kind == "plain"
    return X, np.ascontiguousarray(edges, dtype=np.float32)


@pytest.mark.parametrize("max_bins", [2, 32, 256])
@pytest.mark.parametrize(
    "kind",
    ["plain", "on_edges", "duplicate_edges", "pos_inf", "neg_inf",
     "neg_zero", "nan"],
)
def test_bin_features_equals_searchsorted_right(kind, max_bins):
    X, edges = _case(kind, max_bins)
    got = bin_features(jnp.asarray(X), jnp.asarray(edges))
    assert got.dtype == jnp.int32
    assert got.shape == X.shape
    got = np.asarray(got)
    np.testing.assert_array_equal(got, _searchsorted_bins(X, edges))
    assert got.min() >= 0 and got.max() <= max_bins - 1
    if kind == "nan":
        assert (got[::5, 0] == max_bins - 1).all()
    if kind == "neg_zero":
        assert (got[::4, 0] == got[1::4, 0]).all()


def test_bin_features_lowers_without_gather_sort_or_loop():
    """Pins the lowering: the serial search must not come back unseen.  A
    ``searchsorted`` lowers to a ``while`` whose body gathers (or, by its
    other methods, to a ``sort``); the compare-and-count is elementwise."""
    X = jax.ShapeDtypeStruct((4096, 78), jnp.float32)
    edges = jax.ShapeDtypeStruct((78, 31), jnp.float32)
    text = bin_features.lower(X, edges).as_text()
    ops = set(re.findall(r"stablehlo\.([a-z_]+)", text))
    assert ops, "no StableHLO operations found in the lowered text"
    assert not ops & {"gather", "dynamic_gather", "while", "sort", "scatter",
                      "dynamic_slice", "custom_call"}, sorted(ops)
    assert "compare" in ops
    # and no [N, F, B] temporary: every tensor is at most two-dimensional
    assert not re.search(r"tensor<\d+x\d+x\d+", text)


def _forest_fit(frame, mesh):
    from sntc_tpu.core.base import Pipeline
    from sntc_tpu.data.schema import CICIDS2017_FEATURES
    from sntc_tpu.feature import ChiSqSelector, StringIndexer, VectorAssembler
    from sntc_tpu.models import RandomForestClassifier

    model = Pipeline(stages=[
        StringIndexer(inputCol="Label", outputCol="label",
                      handleInvalid="skip"),
        VectorAssembler(inputCols=list(CICIDS2017_FEATURES),
                        outputCol="rawFeatures", handleInvalid="skip"),
        ChiSqSelector(mesh=mesh, numTopFeatures=40,
                      featuresCol="rawFeatures", labelCol="label",
                      outputCol="features"),
        RandomForestClassifier(mesh=mesh, numTrees=20, maxDepth=5,
                               maxBins=32, seed=11, featuresCol="features"),
    ]).fit(frame)
    stages = model.getStages()
    forest = stages[-1].forest
    return {
        "selected_features": np.asarray(stages[2].selected_features),
        "feature": np.asarray(forest.feature),
        "threshold": np.asarray(forest.threshold),
        "leaf_stats": np.asarray(forest.leaf_stats),
    }


def test_forest_fit_equals_fit_on_searchsorted_bins(monkeypatch):
    """The whole-fit form of "same answers": ChiSqSelector ->
    RandomForestClassifier at the benchmark's rehearsal shape (12,000 x 78
    rows, top 40, 20 trees x depth 5) fits the same selection and the same
    forest as the same program fed bin ids from ``np.searchsorted``."""
    from sntc_tpu.data.synth import generate_frame
    from sntc_tpu.feature import chisq_selector
    from sntc_tpu.models.tree import random_forest
    from sntc_tpu.parallel import default_mesh

    binned_widths = []

    def on_host(x, e):
        binned_widths.append(x.shape[1])
        return _searchsorted_bins(np.asarray(x), np.asarray(e))

    def searchsorted_bins(X, edges):
        return jax.pure_callback(
            on_host, jax.ShapeDtypeStruct(X.shape, jnp.int32), X, edges
        )

    mesh = default_mesh(1)
    got = _forest_fit(generate_frame(12_000, seed=27), mesh)

    # the selector's contingency program is cached per mesh and shape with
    # the binning traced into it: rebuild it around the patched function
    chisq_selector._contingency_agg.cache_clear()
    monkeypatch.setattr(chisq_selector, "bin_features", searchsorted_bins)
    monkeypatch.setattr(random_forest, "bin_features", searchsorted_bins)
    try:
        want = _forest_fit(generate_frame(12_000, seed=27), mesh)
    finally:
        chisq_selector._contingency_agg.cache_clear()

    # both call sites took the patched path: the selector's 78 columns,
    # then the forest's 40 selected ones
    assert binned_widths == [78, 40]
    assert len(got["selected_features"]) == 40
    assert (got["feature"] >= 0).sum() > 20  # real trees, not stumps
    for name in ("selected_features", "feature", "threshold", "leaf_stats"):
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
