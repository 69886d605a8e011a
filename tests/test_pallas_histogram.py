"""Pallas histogram kernel vs the segment-sum reference (interpret mode on
CPU; the same kernel compiles for TPU via mosaic)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sntc_tpu.ops.pallas_histogram import (
    _VMEM_LIMIT,
    _plan,
    _split3,
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
    """Per-tree statistics ``[T, S, N]`` (the boosted trees) through the
    grower's ``lax.map`` against the same statistics shared."""
    rng = np.random.default_rng(5)
    n, f, s, n_nodes, n_bins = 400, 4, 3, 4, 16
    binned_t = jnp.asarray(
        rng.integers(0, n_bins, size=(f, n)).astype(np.int32)
    )
    node_idx = jnp.asarray(
        rng.integers(-1, n_nodes, size=(t, n)).astype(np.int32)
    )
    stats_t = rng.normal(size=(t, s, n)).astype(np.float32)
    weight = jnp.asarray(rng.random((t, n)).astype(np.float32))
    call = lambda ni, st, w: level_histogram_pallas(  # noqa: E731
        binned_t, ni, st, w, n_nodes=n_nodes, n_bins=n_bins,
        tile_n=128, interpret=True,
    )
    mapped = jax.lax.map(
        lambda a: call(*a), (node_idx, jnp.asarray(stats_t), weight)
    )
    for i in range(t):
        np.testing.assert_array_equal(
            np.asarray(mapped[i]),
            np.asarray(call(node_idx[i], jnp.asarray(stats_t[i]),
                            weight[i])),
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
    node_chunk, _, _, _ = _plan(f, n, -(-s // 8) * 8, n_nodes, 16)
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


@pytest.mark.parametrize("f,s_pad,n_nodes,n_bins", [
    (40, 16, 128, 32), (40, 8, 8, 128), (40, 16, 8, 32), (78, 16, 1, 32),
    (40, 16, 255, 32), (40, 8, 63, 128), (8, 256, 8, 32),
    (40, 24, 32, 32), (40, 24, 128, 32), (40, 40, 32, 32), (40, 56, 64, 32),
    (40, 104, 32, 32),
])
def test_plan_stays_inside_vmem(f, s_pad, n_nodes, n_bins):
    """At every width the guard admits the planned step (double-buffered
    blocks, the accumulator's contribution, the one-hot and ``A_t`` with
    their float32 intermediates) stays under the kernel's VMEM limit."""
    assert hist_fits_pallas(n_nodes, n_bins)
    b_pad = -(-n_bins // 16) * 16
    node_chunk, cols, f_block, tile = _plan(
        f, 4063232, s_pad, n_nodes, b_pad
    )
    # a column block that is not the whole array is lane-aligned (the
    # Mosaic lowering refuses any other; interpret mode does not look)
    assert cols >= 3 * node_chunk * s_pad
    assert cols % 128 == 0 or node_chunk >= n_nodes
    rows = f_block * b_pad
    blocks = 2 * 4 * tile * (f_block + 8 + 8 + s_pad)
    acc = 3 * 4 * rows * cols  # two buffers and the step's contribution
    values = tile * (10 * rows + 10 * cols)
    assert tile % 128 == 0 and node_chunk >= 1
    assert f_block == f or f_block % 8 == 0
    assert 4063232 % tile == 0  # no ragged tail at the cell's size
    # no candidate divides 1000: one padded copy at the largest tile
    # that fits, not the slowest step size
    assert _plan(f, 1000, s_pad, n_nodes, b_pad)[3] == tile
    assert _plan(f, 31 * 256, s_pad, n_nodes, b_pad)[3] == min(tile, 256)
    assert blocks + acc + values <= _VMEM_LIMIT


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


@pytest.mark.parametrize("f,s,n_nodes,n_bins", [
    (40, 15, 8, 32),  # the benchmark cell's deepest level
    (78, 15, 1, 32),  # the chi-square contingency
    (40, 15, 128, 32),  # the guard's edge: four node chunks
    (40, 3, 8, 128),  # the boosted regressors
    (40, 20, 32, 32),  # S_pad 24: chunk columns rounded up to lanes
    (40, 40, 64, 32),  # S_pad 40
    (40, 3, 63, 128),  # widest one-chunk level at 128 bins
    (78, 3, 1, 32),  # the boosted cell: per-tree S 3, all 78 features
    (78, 3, 8, 32),  # its deepest level (8 histogrammed nodes)
])
def test_kernel_compiles_for_the_chip(v5e_chip, f, s, n_nodes, n_bins):
    """The Mosaic lowering takes the planned blocks at the widths the
    guard admits, multi-chunk levels with S_pad not a power of two
    among them."""
    assert hist_fits_pallas(n_nodes, n_bins)
    n = 65536

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    compiled = jax.jit(
        lambda bt, ni, st, w: level_histogram_pallas(
            bt, ni, st, w, n_nodes=n_nodes, n_bins=n_bins, interpret=False
        )
    ).lower(
        sds((f, n), jnp.int32), sds((n,), jnp.int32),
        sds((s, n), jnp.float32), sds((n,), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
