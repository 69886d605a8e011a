"""Pallas histogram kernel vs the segment-sum reference (interpret mode on
CPU; the same kernel compiles for TPU via mosaic)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sntc_tpu.ops.pallas_histogram import (
    _MAX_COLUMNS,
    _VMEM_LIMIT,
    _plan,
    _split3,
    column_tiles,
    hist_fits_pallas,
    level_histogram_pallas,
)


def _reference(binned, node_idx, stats, n_nodes, n_bins, dtype=np.float32):
    """The plain loop over rows; ``stats`` ``[N, S]`` pre-weighted."""
    f = binned.shape[1]
    out = np.zeros((f, n_nodes * n_bins, stats.shape[1]), dtype)
    for j in range(f):
        for i in range(binned.shape[0]):
            if node_idx[i] >= 0:
                out[j, node_idx[i] * n_bins + binned[i, j]] += stats[i]
    return out


def _segment_twin(binned, node_idx, stats, n_nodes, n_bins):
    """The XLA fallback's form: one ``segment_sum`` a feature."""
    data = jnp.asarray(stats * (node_idx >= 0)[:, None])
    ids = jnp.asarray(np.maximum(node_idx, 0)[:, None] * n_bins + binned)
    return np.asarray(jax.vmap(
        lambda i: jax.ops.segment_sum(
            data, i, num_segments=n_nodes * n_bins
        ),
        in_axes=1,
    )(ids))


def _kernel(binned, node_idx, stats_t, weight, **kw):
    """One tree's level: the T = 1 case of the stacked call."""
    return _stacked(
        binned, node_idx[None], stats_t, weight[None], **kw
    )[0]


def _stacked(binned, node_idx, stats_t, weight, **kw):
    """A level's ``T`` trees in one call: ``node_idx`` / ``weight``
    ``[T, N]``, ``stats_t`` ``[S, N]`` shared or ``[S, T, N]`` per tree."""
    return np.asarray(
        level_histogram_pallas(
            jnp.asarray(binned.T.copy()), jnp.asarray(node_idx),
            jnp.asarray(stats_t), jnp.asarray(weight),
            interpret=True, **kw,
        )
    )


@pytest.mark.parametrize("n,f,s,n_nodes,n_bins", [
    (300, 5, 3, 4, 8),
    (1000, 7, 15, 8, 32),
    (64, 2, 1, 1, 32),
])
def test_matches_reference(n, f, s, n_nodes, n_bins):
    rng = np.random.default_rng(0)
    binned = rng.integers(0, n_bins, size=(n, f)).astype(np.int32)
    node_idx = rng.integers(-1, n_nodes, size=n).astype(np.int32)
    stats = rng.normal(size=(n, s)).astype(np.float32)
    weight = rng.random(n).astype(np.float32)

    got = _kernel(
        binned, node_idx, stats.T.copy(), weight,
        n_nodes=n_nodes, n_bins=n_bins, tile_n=256,
    )
    want = _reference(
        binned, node_idx, stats * weight[:, None], n_nodes, n_bins
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,f,s,n_nodes,n_bins", [
    (333, 5, 1, 1, 4),  # F not a multiple of 8, N not of the tile
    (700, 13, 3, 2, 32),
    (1000, 7, 15, 8, 32),  # the cell's deepest level
    (900, 3, 15, 128, 32),  # the guard's edge: several node chunks
    (500, 9, 3, 8, 128),  # the boosted regressors' shape
    (260, 40, 15, 1, 32),  # dead rows with one node
])
def test_integer_stats_array_equal(n, f, s, n_nodes, n_bins):
    """Integer-valued weighted statistics (Poisson bagging weights times
    one-hot labels or small counts): every product and every partial sum
    is an exact small integer, so the kernel, the loop and the
    ``segment_sum`` twin agree on every element."""
    rng = np.random.default_rng(n)
    binned = rng.integers(0, n_bins, size=(n, f)).astype(np.int32)
    node_idx = rng.integers(-1, n_nodes, size=n).astype(np.int32)
    stats = rng.integers(0, 4, size=(n, s)).astype(np.float32)
    weight = rng.integers(0, 7, size=n).astype(np.float32)

    got = _kernel(
        binned, node_idx, stats.T.copy(), weight,
        n_nodes=n_nodes, n_bins=n_bins, tile_n=256,
    )
    weighted = stats * weight[:, None]
    np.testing.assert_array_equal(
        got, _reference(binned, node_idx, weighted, n_nodes, n_bins)
    )
    np.testing.assert_array_equal(
        got, _segment_twin(binned, node_idx, weighted, n_nodes, n_bins)
    )


def _adversarial_f32(rng, n):
    """float32 values that stress the three-term split: all 24
    significant bits set, exact ties of the bfloat16 rounding (at the
    first and at the second cut), mixed signs, 1e-30 to 1e30."""
    mant_full = np.float32(2.0) - np.float32(2.0 ** -23)  # 24 ones
    tie_hi = np.float32(1.0 + 2.0 ** -8)  # halfway between two bf16
    tie_hi_odd = np.float32(1.0 + 3 * 2.0 ** -8)
    tie_mid = np.float32(1.0 + 2.0 ** -7 + 2.0 ** -16)  # tie in x - hi
    specials = np.array(
        [mant_full, tie_hi, tie_hi_odd, tie_mid,
         np.float32(1.0 + 2.0 ** -8 + 2.0 ** -23), np.float32(16777215.0)],
        np.float32,
    )
    # powers of two (2^-99 .. 2^99 = 1.6e-30 .. 6.3e29) keep the ties ties
    scale = np.ldexp(np.float32(1.0), rng.integers(-99, 100, size=n))
    vals = specials[rng.integers(0, len(specials), size=n)]
    sign = rng.choice(np.float32([-1.0, 1.0]), size=n)
    rnd = rng.uniform(1.0, 2.0, size=n).astype(np.float32)
    x = np.where(rng.random(n) < 0.5, vals, rnd) * sign
    return (x * scale).astype(np.float32)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_three_term_split_is_exact(seed):
    x = _adversarial_f32(np.random.default_rng(seed), 4096)
    assert np.isfinite(x).all() and (np.abs(x) > 1e-30).all()
    hi, mid, lo = (np.asarray(t) for t in jax.jit(_split3)(jnp.asarray(x)))
    # bit for bit, and each term is a bfloat16 value already
    np.testing.assert_array_equal(
        ((hi + mid) + lo).view(np.uint32), x.view(np.uint32)
    )
    for t in (hi, mid, lo):
        np.testing.assert_array_equal(
            np.asarray(jnp.asarray(t).astype(jnp.bfloat16)
                       .astype(jnp.float32)), t
        )
    # in float64 the three terms add to x with nothing lost
    np.testing.assert_array_equal(
        hi.astype(np.float64) + mid.astype(np.float64)
        + lo.astype(np.float64), x.astype(np.float64),
    )
    assert (mid != 0).any() and (lo != 0).any()


@pytest.mark.parametrize("magnitude", ["unit", "wide"])
def test_real_stats_within_f32_summation_error(magnitude):
    """Real-valued statistics against a float64 loop: what is left is the
    float32 summation, not a bfloat16 rounding of the statistics (which
    would read 4e-3 here)."""
    rng = np.random.default_rng(7)
    n, f, s, n_nodes, n_bins = 1500, 6, 3, 4, 8
    binned = rng.integers(0, n_bins, size=(n, f)).astype(np.int32)
    node_idx = rng.integers(-1, n_nodes, size=n).astype(np.int32)
    if magnitude == "unit":
        stats = rng.normal(size=(n, s)).astype(np.float32)
    else:  # adversarial mantissas and signs; one magnitude a column, so
        # a cell's float32 sum does not mix 1e-20 with 1e20
        stats = _adversarial_f32(rng, n * s).reshape(n, s)
        stats = np.ldexp(*np.frexp(stats)[:1], 1).astype(np.float32)
        stats = stats * np.float32([1e-20, 1.0, 1e20])
    weight = np.ones(n, np.float32)
    got = _kernel(
        binned, node_idx, stats.T.copy(), weight,
        n_nodes=n_nodes, n_bins=n_bins, tile_n=256,
    )
    want = _reference(
        binned, node_idx, stats, n_nodes, n_bins, dtype=np.float64
    )
    mag = _reference(
        binned, node_idx, np.abs(stats), n_nodes, n_bins, dtype=np.float64
    )
    # a cell sums about n / (n_nodes * n_bins) = 47 terms
    assert (np.abs(got - want) <= 64 * 2.0 ** -24 * mag).all()


@pytest.mark.parametrize("t", [1, 3])
def test_per_tree_stats_match_shared(t):
    """Per-tree statistics ``[S, T, N]`` (the boosted trees) in one
    stacked call against each tree's statistics shared, a call a tree
    (what the grower's ``lax.map`` over trees ran)."""
    rng = np.random.default_rng(5)
    n, f, s, n_nodes, n_bins = 400, 4, 3, 4, 16
    binned = rng.integers(0, n_bins, size=(n, f)).astype(np.int32)
    node_idx = rng.integers(-1, n_nodes, size=(t, n)).astype(np.int32)
    stats_t = rng.normal(size=(s, t, n)).astype(np.float32)
    weight = rng.random((t, n)).astype(np.float32)
    kw = dict(n_nodes=n_nodes, n_bins=n_bins, tile_n=128)
    stacked = _stacked(binned, node_idx, stats_t, weight, **kw)
    assert stacked.shape == (t, f, n_nodes * n_bins, s)
    for i in range(t):
        # float32 sums over the row tiles in the same order: equal bits
        np.testing.assert_array_equal(
            stacked[i],
            _kernel(binned, node_idx[i], stats_t[:, i], weight[i], **kw),
        )


def _level(rng, n, f, s, t, per_tree, n_nodes, n_bins, integer):
    """A level's operands: bins ``[N, F]``, node ids with dead rows and
    weights ``[T, N]``, statistics as the kernel takes them, and the
    same per tree pre-weighted ``[T, N, S]`` for the references."""
    binned = rng.integers(0, n_bins, size=(n, f)).astype(np.int32)
    node_idx = rng.integers(-1, n_nodes, size=(t, n)).astype(np.int32)
    shape = (s, t, n) if per_tree else (s, n)
    if integer:
        stats_t = rng.integers(-3, 4, size=shape).astype(np.float32)
        weight = rng.integers(0, 7, size=(t, n)).astype(np.float32)
    else:
        stats_t = rng.normal(size=shape).astype(np.float32)
        weight = (rng.random((t, n)) * 3).astype(np.float32)
    each = stats_t if per_tree else np.broadcast_to(
        stats_t[:, None, :], (s, t, n)
    )
    weighted = (each * weight[None]).transpose(1, 2, 0)  # [T, N, S]
    return binned, node_idx, stats_t, weight, weighted


@pytest.mark.parametrize("n_nodes", [1, 8])
@pytest.mark.parametrize("per_tree", [False, True], ids=["shared", "own"])
@pytest.mark.parametrize("t", [1, 15, 20])
def test_stacked_trees_equal_the_loop_over_trees(t, per_tree, n_nodes):
    """A level's trees as columns of one product against the call a tree
    it replaces and against ``segment_sum``: integer statistics (signed,
    dead rows) array-equal on every element, real ones within the
    float32 summation error of a float64 loop.  Shared statistics are
    the forests' 15 classes, per-tree ones the boosted trees' 3."""
    s = 3 if per_tree else 15
    n, f, n_bins = 520, 4, 32
    rng = np.random.default_rng(100 * t + n_nodes)
    kw = dict(n_nodes=n_nodes, n_bins=n_bins, tile_n=256)

    binned, node_idx, stats_t, weight, weighted = _level(
        rng, n, f, s, t, per_tree, n_nodes, n_bins, integer=True
    )
    got = _stacked(binned, node_idx, stats_t, weight, **kw)
    assert got.shape == (t, f, n_nodes * n_bins, s)
    for i in range(t):
        one = stats_t[:, i] if per_tree else stats_t
        np.testing.assert_array_equal(
            got[i], _kernel(binned, node_idx[i], one, weight[i], **kw)
        )
        np.testing.assert_array_equal(
            got[i],
            _segment_twin(binned, node_idx[i], weighted[i], n_nodes, n_bins),
        )

    binned, node_idx, stats_t, weight, weighted = _level(
        rng, n, f, s, t, per_tree, n_nodes, n_bins, integer=False
    )
    got = _stacked(binned, node_idx, stats_t, weight, **kw)
    for i in range(0, t, 7):
        want, mag = (
            _reference(binned, node_idx[i], x, n_nodes, n_bins, np.float64)
            for x in (weighted[i], np.abs(weighted[i]))
        )
        # a cell sums at most n / 2 rows
        assert (np.abs(got[i] - want) <= 64 * 2.0 ** -24 * mag + 1e-30).all()


@pytest.mark.parametrize("t,s,per_tree,n_nodes", [
    (20, 3, True, 8),  # one block of 24 trees: four spare
    (100, 3, True, 2),  # two blocks of 56: twelve spare in the last
    (45, 15, False, 1),  # tree-major: two blocks of 24, three spare
    (20, 3, False, 4),  # shared statistics, a statistic a piece
])
def test_spare_trees_of_the_last_block_add_nothing(t, s, per_tree, n_nodes):
    """A tree count that does not fill the last tree block: the block
    reads rows past the arrays' end, and what it finds there reaches no
    tree's histogram."""
    n, f, n_bins = 300, 3, 8
    plan = _plan(f, n, t, s, per_tree, n_nodes, 16)
    assert -(-t // plan.tree_block) * plan.tree_block > t
    assert plan.stat_major == (s == 3)
    rng = np.random.default_rng(t)
    binned, node_idx, stats_t, weight, weighted = _level(
        rng, n, f, s, t, per_tree, n_nodes, n_bins, integer=True
    )
    got = _stacked(
        binned, node_idx, stats_t, weight, n_nodes=n_nodes, n_bins=n_bins,
    )
    assert np.isfinite(got).all()
    for i in range(t):
        np.testing.assert_array_equal(
            got[i],
            _segment_twin(binned, node_idx[i], weighted[i], n_nodes, n_bins),
        )


@pytest.mark.parametrize(
    "n_nodes,s", [(16, 15), (40, 8), (130, 15), (32, 20), (30, 37)]
)
def test_wide_level_and_stacked_terms_recombine(n_nodes, s):
    """Levels wider than one array tile (``n_nodes * S_pad`` > 128), up
    to several node chunks with a ragged last one, and chunks whose
    columns are rounded up to whole lane tiles (S_pad 24 and 40): the
    three stacked terms are added back per (node, stat) column,
    real-valued."""
    rng = np.random.default_rng(n_nodes)
    n, f, n_bins = 600, 3, 4
    node_chunk = _plan(f, n, 1, s, False, n_nodes, 16).node_chunk
    assert n_nodes * (-(-s // 8) * 8) > 128
    assert (n_nodes > node_chunk) == (n_nodes in (130, 32, 30))
    binned = rng.integers(0, n_bins, size=(n, f)).astype(np.int32)
    node_idx = rng.integers(-1, n_nodes, size=n).astype(np.int32)
    stats = rng.normal(size=(n, s)).astype(np.float32)
    weight = (rng.random(n) * 3).astype(np.float32)
    got = _kernel(
        binned, node_idx, stats.T.copy(), weight,
        n_nodes=n_nodes, n_bins=n_bins, tile_n=256,
    )
    want = _reference(
        binned, node_idx, stats * weight[:, None], n_nodes, n_bins,
        dtype=np.float64,
    )
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("n_nodes,n_bins,fits", [
    (128, 32, True), (256, 32, False), (1, 32, True), (8, 128, True),
    (63, 128, True), (64, 128, False), (255, 32, True),
])
def test_hist_fits_pallas_verdicts(n_nodes, n_bins, fits):
    assert hist_fits_pallas(n_nodes, n_bins) is fits


@pytest.mark.parametrize("f,s,n_nodes,n_bins,t,per_tree", [
    (40, 16, 128, 32, 1, False), (40, 8, 8, 128, 1, False),
    (40, 16, 8, 32, 1, False), (78, 16, 1, 32, 1, False),
    (40, 16, 255, 32, 1, False), (40, 8, 63, 128, 1, False),
    (8, 256, 8, 32, 1, False), (40, 24, 32, 32, 1, False),
    (40, 24, 128, 32, 1, False), (40, 40, 32, 32, 1, False),
    (40, 56, 64, 32, 1, False), (40, 104, 32, 32, 1, False),
    # a level's trees stacked: the two cells' shapes ...
    (78, 3, 1, 32, 15, True), (78, 3, 8, 32, 15, True),
    (40, 15, 1, 32, 20, False), (40, 15, 8, 32, 20, False),
    # ... and tree blocks at S_pad 8 / 16 / 24 / 40, T 15 / 20 / 100
    (40, 8, 8, 32, 15, False), (40, 16, 128, 32, 20, False),
    (40, 24, 4, 32, 100, False), (40, 40, 16, 32, 100, False),
    (40, 40, 1, 32, 15, False), (78, 3, 128, 32, 100, True),
    (40, 3, 8, 128, 20, False), (78, 24, 2, 256, 20, True),
])
def test_plan_stays_inside_vmem(f, s, n_nodes, n_bins, t, per_tree):
    """At every width the guard admits the planned step (double-buffered
    blocks, the accumulator's contribution, the one-hot and ``A_t`` with
    their float32 intermediates) stays under the kernel's VMEM limit,
    whatever the trees a step."""
    assert hist_fits_pallas(n_nodes, n_bins)
    b_pad = -(-n_bins // 16) * 16
    plan = _plan(f, 4063232, t, s, per_tree, n_nodes, b_pad)
    tb, nc, cols, tile = (
        plan.tree_block, plan.node_chunk, plan.cols, plan.tile_n
    )
    steps = -(-t // tb) * -(-n_nodes // nc)
    unit = 3 * s if plan.stat_major else 3 * -(-s // 8) * 8
    # a column block that is not the whole array is lane-aligned (the
    # Mosaic lowering refuses any other; interpret mode does not look)
    assert cols >= unit * tb * nc
    assert cols % 128 == 0 or steps == 1
    # the node ids and the weights are blocked [tree_block, tile], and a
    # statistic of a block of trees is whole sublane tiles
    assert tb % 8 == 0 or (tb == t and not plan.stat_major)
    assert cols <= _MAX_COLUMNS or (nc == 1 and tb <= 8)
    assert column_tiles(t, s, per_tree, n_nodes) == (
        steps * -(-cols // 128), 3 * t * n_nodes * s
    )
    rows = plan.f_block * b_pad
    tb8, s8 = -(-tb // 8) * 8, -(-s // 8) * 8
    blocks = 2 * 4 * tile * (
        plan.f_block + 2 * tb8 + (s * tb8 if per_tree and t > 1 else s8)
    )
    acc = 3 * 4 * rows * cols  # two buffers and the step's contribution
    values = tile * (10 * rows + 10 * cols)
    assert tile % 128 == 0 and nc >= 1
    assert plan.f_block == f or plan.f_block % 8 == 0
    assert 4063232 % tile == 0  # no ragged tail at the cell's size
    # no candidate divides 1000: one padded copy at the largest tile
    # that fits, not the slowest step size
    assert _plan(f, 1000, t, s, per_tree, n_nodes, b_pad).tile_n == tile
    assert _plan(f, 31 * 256, t, s, per_tree, n_nodes, b_pad).tile_n == min(
        tile, 256
    )
    assert blocks + acc + values <= _VMEM_LIMIT


@pytest.mark.parametrize("t,s,per_tree,tiles", [
    # the boosted cell: 15 trees x 3 statistics, a statistic of 16 trees
    # a piece (144 columns a node): 2 + 2 + 3 + 5 + 9 tiles a round
    (15, 3, True, (2, 3, 5, 9)),
    # the forest cell: 20 trees x 16 rows (960 columns a node, two nodes
    # a step): 8 + 8 + 15 + 30 + 60 tiles a fit
    (20, 15, False, (8, 15, 30, 60)),
    # one tree a call, as before this kernel took a tree axis
    (1, 3, True, (1, 1, 1, 2)), (1, 15, False, (1, 1, 2, 3)),
])
def test_column_tiles_of_the_cells(t, s, per_tree, tiles):
    for n_nodes, want in zip((1, 2, 4, 8), tiles):
        got, live = column_tiles(t, s, per_tree, n_nodes)
        assert got == want and live == 9 * t * n_nodes * s // 3


def test_rf_identical_forest_under_pallas_hist(mesh8, monkeypatch):
    """The grower must produce the SAME trees with either histogram impl."""
    from sntc_tpu.core.frame import Frame
    from sntc_tpu.models import RandomForestClassifier

    rng = np.random.default_rng(3)
    X = rng.normal(size=(600, 5)).astype(np.float32)
    y = (X[:, 0] + X[:, 2] > 0).astype(np.float64)
    f = Frame({"features": X, "label": y})
    kw = dict(mesh=mesh8, numTrees=3, maxDepth=3, seed=0)

    monkeypatch.setenv("SNTC_TREE_HIST", "segment")
    m_seg = RandomForestClassifier(**kw).fit(f)
    monkeypatch.setenv("SNTC_TREE_HIST", "pallas")
    m_pal = RandomForestClassifier(**kw).fit(f)

    np.testing.assert_array_equal(m_pal.forest.feature, m_seg.forest.feature)
    np.testing.assert_allclose(
        m_pal.forest.leaf_stats, m_seg.forest.leaf_stats, rtol=1e-5, atol=1e-5
    )


def test_ovr_round_identical_trees_under_pallas_hist(mesh8, monkeypatch):
    """A one-vs-rest boosted fit, 15 classes riding the grower's tree axis
    with per-tree statistics ``[15, 3, N]``: the kernel's one stacked call
    a level grows the trees ``segment_sum`` grows.  The first round's
    residuals are +-1 (the margin starts at 0), so its histograms are
    exact on both; the second round's are real-valued."""
    from sntc_tpu.core.frame import Frame
    from sntc_tpu.models import GBTClassifier, OneVsRest
    from sntc_tpu.obs import registry

    rng = np.random.default_rng(11)
    k, n = 15, 960
    y = rng.integers(0, k, size=n)
    X = rng.normal(size=(n, 6)).astype(np.float32)
    X[:, 0] += (y % 4) * 1.5
    X[:, 1] += (y // 4) * 1.5
    f = Frame({"features": X, "label": y.astype(np.float64)})

    def fit():
        clf = GBTClassifier(mesh=mesh8, maxIter=2, maxDepth=3, seed=5)
        return [m.forest for m in OneVsRest(classifier=clf).fit(f).models]

    def counted(name):
        return registry().get(name) or 0

    monkeypatch.setenv("SNTC_TREE_HIST", "segment")
    seg = fit()
    monkeypatch.setenv("SNTC_TREE_HIST", "pallas")
    tiles0, cols0 = (
        counted("sntc_kernel_tree_hist_column_tiles_total"),
        counted("sntc_kernel_tree_hist_columns_total"),
    )
    pal = fit()
    # two rounds of levels 1, 1, 2 histogrammed nodes: 144 -> 2, 2 and
    # 288 -> 3 tiles; 3 terms x 15 trees x 4 nodes x 3 statistics
    assert counted("sntc_kernel_tree_hist_column_tiles_total") - tiles0 == 14
    assert counted("sntc_kernel_tree_hist_columns_total") - cols0 == 1080
    assert len(pal) == k
    from sntc_tpu.models.tree.grower import forest_leaf_stats

    def leaf_means(fo):  # [2, n]: each round's leaf mean of every row
        return np.asarray(forest_leaf_stats(
            jnp.asarray(X), jnp.asarray(fo.feature),
            jnp.asarray(fo.threshold), jnp.asarray(fo.leaf_stats),
            max_depth=3, value=True,
        ))

    for a, b in zip(pal, seg):
        assert (a.feature[0] >= 0).any()
        for arr in ("feature", "threshold", "leaf_stats"):
            np.testing.assert_array_equal(
                getattr(a, arr)[0], getattr(b, arr)[0]
            )
        # round two sums real residuals in another order: a split may
        # move across a bin no row lies in, or a node whose gain is
        # rounding split on one side only; no row's step moves
        np.testing.assert_allclose(
            leaf_means(a), leaf_means(b), rtol=1e-4, atol=1e-4
        )


def test_forest_deeper_than_the_guard_shrinks_its_group(mesh8, monkeypatch):
    """Depth deep enough that the widest level (256 nodes x 32 bins)
    overflows the kernel's guard: under the kernel the node group is cut
    to the guard (128) and the level runs in two kernel passes, never on
    ``segment_sum``, with sibling subtraction on every level below the
    root.  The grown forest must equal the all-segment one.

    Exact equality is safe, not flaky: Poisson bagging weights are
    integer-valued, so every histogram cell is an exact small-int f32
    sum on BOTH impls (the sibling subtraction parent − left is exact
    on integers), identical cells feed the identical split-eval code,
    and the gain argmaxes cannot diverge."""
    from sntc_tpu.core.frame import Frame
    from sntc_tpu.models import RandomForestClassifier
    from sntc_tpu.models.tree.grower import _level_plan

    assert hist_fits_pallas(128, 32) and not hist_fits_pallas(256, 32)

    rng = np.random.default_rng(21)
    n = 800
    X = rng.normal(size=(n, 8)).astype(np.float32)
    # noisy labels: the trees keep splitting down to the 256-node level
    noise = rng.normal(size=(2, n))
    y = ((X[:, 0] + noise[0] > 0) * 2
         + (X[:, 3] + noise[1] > 0.2)).astype(np.float64)
    f = Frame({"features": X, "label": y})
    kw = dict(mesh=mesh8, numTrees=2, maxDepth=9, seed=0,
              featureSubsetStrategy="all")

    monkeypatch.setenv("SNTC_TREE_HIST", "segment")
    assert _level_plan(2, 8, 32, 4, 9, mesh8) == (
        "segment", 1 << 15, (False,) * 9
    )
    m_seg = RandomForestClassifier(**kw).fit(f)
    monkeypatch.setenv("SNTC_TREE_HIST", "pallas")
    assert _level_plan(2, 8, 32, 4, 9, mesh8) == (
        "pallas", 128, (True,) * 8 + (False,)
    )
    m_pal = RandomForestClassifier(**kw).fit(f)

    # the 256-node level is really there, and splits
    assert (m_seg.forest.feature[:, 255:511] >= 0).any()
    np.testing.assert_array_equal(
        m_pal.forest.feature, m_seg.forest.feature
    )
    np.testing.assert_array_equal(
        m_pal.forest.leaf_stats, m_seg.forest.leaf_stats
    )


def test_row_padding_contributes_zero():
    # n not a multiple of tile_n exercises the padding path
    n, f, s, n_nodes, n_bins = 130, 3, 2, 2, 4
    rng = np.random.default_rng(1)
    binned = rng.integers(0, n_bins, size=(n, f)).astype(np.int32)
    node_idx = rng.integers(0, n_nodes, size=n).astype(np.int32)
    got = _kernel(
        binned, node_idx, np.ones((s, n), np.float32),
        np.ones(n, np.float32), n_nodes=n_nodes, n_bins=n_bins, tile_n=128,
    )
    assert got.sum() == pytest.approx(n * s * f)


@pytest.fixture(scope="module")
def v5e_chip():
    """A described (not attached) v5e chip: the TPU's compiler is
    installed wherever the tests run, and refuses what interpret mode
    lets through (block shapes off the lane tiling, too much VMEM)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("f,s,n_nodes,n_bins,t,per_tree", [
    (40, 15, 8, 32, 1, False),  # one tree of the forest's deepest level
    (78, 15, 1, 32, 1, False),  # the chi-square contingency
    (40, 15, 128, 32, 1, False),  # the guard's edge: four node chunks
    (40, 3, 8, 128, 1, False),  # the boosted regressors
    (40, 20, 32, 32, 1, False),  # S_pad 24: chunk columns rounded up
    (40, 40, 64, 32, 1, False),  # S_pad 40
    (40, 3, 63, 128, 1, False),  # widest one-chunk level at 128 bins
    (78, 3, 1, 32, 1, True),  # a binary boosted tree, all 78 features
    (78, 3, 8, 32, 1, True),  # its deepest level (8 histogrammed nodes)
    # what the two cells compile: a level's trees in one call
    (78, 3, 1, 32, 15, True),  # cicflow_gbt.fit: levels 0 and 1
    (78, 3, 8, 32, 15, True),  # its deepest level: 1,152 columns
    (40, 15, 1, 32, 20, False),  # cicflow_rf.fit: 960 columns
    (40, 15, 8, 32, 20, False),  # its deepest level: four node chunks
    (40, 3, 4, 32, 20, False),  # a regression forest: shared, S 3
    (40, 15, 2, 32, 45, False),  # tree blocks with spare trees
])
def test_kernel_compiles_for_the_chip(
    v5e_chip, f, s, n_nodes, n_bins, t, per_tree
):
    """The Mosaic lowering takes the planned blocks at the widths the
    guard admits: multi-chunk levels with S_pad not a power of two, tree
    blocks taller than the arrays they read, statistics blocks past the
    last statistic."""
    assert hist_fits_pallas(n_nodes, n_bins)
    n = 65536

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    compiled = jax.jit(
        lambda bt, ni, st, w: level_histogram_pallas(
            bt, ni, st, w, n_nodes=n_nodes, n_bins=n_bins, interpret=False
        )
    ).lower(
        sds((f, n), jnp.int32), sds((t, n), jnp.int32),
        sds((s, t, n) if per_tree else (s, n), jnp.float32),
        sds((t, n), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape,dtype", [
    ((4_058_236, 78), jnp.float32),  # the cells' matrix on one chip
    ((4_043_247, 78), jnp.float32),  # the whole set's last shard of four
    ((4_058_236, 40), jnp.float32),  # the forest's selected columns
    ((4_058_236,), jnp.float32),     # a label vector
    ((4_058_236,), jnp.int32),
])
def test_shard_pad_program_only_moves_bytes_on_the_chip(
    v5e_chip, shape, dtype
):
    """``collectives._pad_shard_rows`` at the cells' sizes, compiled for
    the chip: no arithmetic (the compiler turns a ``concatenate`` into a
    ``maximum``, which flushes denormals and rewrites NaN payloads on the
    TPU: read there, PR 36; no CPU run can see it), no temporaries, the
    padded shard out."""
    from sntc_tpu.parallel.collectives import _pad_shard_rows

    rows = 4_063_232
    compiled = _pad_shard_rows.lower(
        jax.ShapeDtypeStruct((1,) + shape[1:], dtype, sharding=v5e_chip),
        jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip),
        rows=rows,
    ).compile()
    text = compiled.as_text()
    assert "maximum" not in text and "minimum" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes == 0
    assert mem.output_size_in_bytes >= rows * math.prod(shape[1:]) * 4
