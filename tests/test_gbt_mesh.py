"""The one-vs-rest boosted loop under a four-device mesh, as the four-chip
deployment of the benchmark (``cicflow_gbt_whole``) runs it: rows sharded
over the mesh, the ``tree_hist`` kernel per shard (the Pallas interpreter
here), one ``psum`` a level.

* the trees of a 4-device fit are the 1-device fit's (the shards' partial
  sums add in another order, so leaf values agree to float32 rounding);
* no device program of the loop, lowered for the 4-device mesh, holds a
  collective other than the histograms' all-reduces, and each hands its
  rows on sharded as it took them: nothing is gathered, nothing
  replicated;
* the whole set's padded rows a chip are the one-chip cells' padded rows,
  so the two cells compile the same per-shard shapes.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from sntc_tpu.models import GBTClassifier
from sntc_tpu.models.tree import gbt, grower
from sntc_tpu.ops.binning import bin_features
from sntc_tpu.parallel.collectives import pad_rows
from sntc_tpu.parallel.mesh import DATA_AXIS, default_mesh

K, F, B, D = 3, 8, 8, 3
N = 4 * 512
COLLECTIVE = re.compile(
    r"\b(all-gather|all-to-all|collective-permute|reduce-scatter|all-reduce)"
    r"(-start)?\("
)


def test_whole_set_shards_are_the_one_chip_cells_rows():
    """16,232,943 rows over 4 chips pad to the rows the one-chip cells
    (4,058,236, a 4-chip share) pad to: the same compiled shapes a chip."""
    one_chip = pad_rows(4_058_236, 1)
    assert one_chip == 4_063_232
    assert pad_rows(16_232_943, 4) == 4 * one_chip


def _boost(mesh, X, y):
    clf = GBTClassifier(mesh=mesh, maxIter=4, maxDepth=D, maxBins=B, seed=5)
    return gbt.fit_gbt_ovr_vectorized(
        clf, X, y, np.ones(len(y), np.float32), K, mesh
    )


def test_four_device_fit_grows_the_one_device_trees(monkeypatch):
    from sntc_tpu.obs import registry

    monkeypatch.setenv("SNTC_TREE_HIST", "pallas")
    rng = np.random.default_rng(11)
    n = 6_000
    y = rng.integers(0, K, n).astype(np.int32)
    X = (rng.normal(size=(n, F)) + 1.5 * np.eye(K, F)[y]).astype(np.float32)
    one = _boost(default_mesh(1), X, y)
    psums = registry().get("sntc_kernel_tree_hist_psum_total")
    four = _boost(default_mesh(4), X, y)
    # the 4-device fit went through the psum: one a level a round
    assert registry().get("sntc_kernel_tree_hist_psum_total") == psums + 4 * D
    for a, b in zip(one, four):
        fa, fb = a.forest, b.forest
        np.testing.assert_array_equal(fa.feature, fb.feature)
        np.testing.assert_array_equal(fa.threshold, fb.threshold)
        # unit weights: a leaf's row count is an integer, exact in float32
        np.testing.assert_array_equal(
            fa.leaf_stats[..., 0], fb.leaf_stats[..., 0]
        )
        # sums of residuals, added shard by shard in another order
        np.testing.assert_allclose(
            fa.leaf_stats[..., 1:], fb.leaf_stats[..., 1:],
            rtol=2e-5, atol=2e-5,
        )
        np.testing.assert_array_equal(a.treeWeights, b.treeWeights)


@pytest.fixture(scope="module")
def mesh4():
    return default_mesh(4)


def _sds(mesh, shape, dtype, spec):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, spec)
    )


def _programs(mesh):
    """``{name: (jitted function, args, kwargs, output spec)}``: every
    device program of ``fit_gbt_ovr_vectorized``'s loop, with operands
    sharded as the loop hands them over."""
    ax = DATA_AXIS
    H = (1 << (D + 1)) - 1
    rows, kn = P(ax), P(None, ax)
    xs = _sds(mesh, (N, F), jnp.float32, P(ax, None))
    ys = _sds(mesh, (N,), jnp.int32, rows)
    ws = _sds(mesh, (N,), jnp.float32, rows)
    y_signed = _sds(mesh, (K, N), jnp.float32, kn)
    edges = _sds(mesh, (F, B - 1), jnp.float32, P())
    heaps = (
        _sds(mesh, (K, H), jnp.int32, P()),
        _sds(mesh, (K, H), jnp.float32, P()),
        _sds(mesh, (K, H, 3), jnp.float32, P()),
    )
    return {
        "bin_features": (bin_features, (xs, edges), {}, P(ax, None)),
        "ovr_signed_labels": (
            gbt._ovr_signed_labels, (ys,), {"num_classes": K}, kn),
        "broadcast_classes": (
            gbt._broadcast_classes, (ws,), {"num_classes": K}, kn),
        "label_stats": (
            gbt._label_stats, (y_signed, ws), {}, P(None, None, ax)),
        "residual_stats": (
            gbt._residual_stats, (y_signed, ws, y_signed), {},
            P(None, None, ax)),
        "binned_transpose": (
            jax.jit(jnp.transpose),
            (_sds(mesh, (N, F), jnp.int32, P(ax, None)),), {}, kn),
        "forest_leaf_stats": (
            grower.forest_leaf_stats, (xs,) + heaps,
            {"max_depth": D, "value": True}, kn),
        "margin_update": (
            jax.jit(lambda m, v: m + 0.1 * v), (y_signed, y_signed), {}, kn),
    }


def _spec(sharding, ndim):
    """A sharding's spec padded to ``ndim`` entries (``P('data')`` of a
    matrix is ``P('data', None)``)."""
    spec = tuple(sharding.spec)
    return spec + (None,) * (ndim - len(spec))


@pytest.mark.parametrize("name", [
    "bin_features", "ovr_signed_labels", "broadcast_classes", "label_stats",
    "residual_stats", "binned_transpose", "forest_leaf_stats",
    "margin_update",
])
def test_loop_program_keeps_rows_sharded(mesh4, name):
    fn, args, kwargs, want = _programs(mesh4)[name]
    compiled = fn.lower(*args, **kwargs).compile()
    found = sorted({m.group(1) for m in COLLECTIVE.finditer(compiled.as_text())})
    assert found == [], found
    (out,) = jax.tree.leaves(compiled.output_shardings)
    ndim = len(tuple(want))
    assert _spec(out, ndim) == tuple(want), out


def test_grower_holds_the_histogram_all_reduces_alone(mesh4, monkeypatch):
    monkeypatch.setenv("SNTC_TREE_HIST", "pallas")
    ax = DATA_AXIS
    plan = grower._level_plan(K, F, B, 3, D, mesh4, True)
    assert plan.hist_impl == "pallas"
    scalar = _sds(mesh4, (), jnp.float32, P())
    compiled = grower._grow_fused.lower(
        _sds(mesh4, (F, N), jnp.int32, P(None, ax)),
        _sds(mesh4, (K, 3, N), jnp.float32, P(None, None, ax)),
        None, None,
        _sds(mesh4, (K, N), jnp.float32, P(None, ax)),
        _sds(mesh4, (F, B - 1), jnp.float32, P()),
        _sds(mesh4, (D, 2), jnp.uint32, P()),
        scalar, scalar,
        max_depth=D, n_bins=B, impurity="variance", subset_k=F, plan=plan,
        mesh=mesh4,
    ).compile()
    found = [m.group(1) for m in COLLECTIVE.finditer(compiled.as_text())]
    # one all-reduce a level (every node of a level in one group), and
    # no other collective: the rows' node ids never leave their shard
    assert found == ["all-reduce"] * D, found
    for out in jax.tree.leaves(compiled.output_shardings):
        assert out.is_fully_replicated  # the forest's heaps, a few KB
