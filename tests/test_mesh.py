"""r22 mesh substrate: sharded-vs-single-device equivalence matrix,
elastic resize / OOM split, transfer-ledger attribution, evidence
metrics, and the axis-registry drift check.

The matrix pins the tentpole claim: every collective call site produces
the SAME answer on a mesh of 1, 2, and 8 (faked CPU) devices — bitwise
for f64 / integer-valued payloads (psum of exact integers is
order-independent), ≤1e-5 relative for f32 iterative fits.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import sntc_tpu.resilience as R
from sntc_tpu.core.frame import Frame
from sntc_tpu.obs.metrics import registry
from sntc_tpu.parallel import (
    default_mesh,
    make_tree_aggregate,
    set_collective_domain,
    shard_batch,
)
from sntc_tpu.parallel.mesh import (
    DATA_AXIS,
    MESH_AXES,
    collective_wire_bytes,
    data_sharding,
    map_at,
    map_reduce_at,
    payload_nbytes,
    sharded_jit,
)

MESH_SIZES = (1, 2, 8)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_state():
    R.clear()
    R.clear_events()
    set_collective_domain(None)
    yield
    R.clear()
    R.clear_events()
    set_collective_domain(None)


def _get(name, **labels):
    return registry().get(name, **labels) or 0


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# substrate units
# ---------------------------------------------------------------------------


def test_mesh_axes_registry_sane():
    assert set(MESH_AXES) == {"data", "model"}
    assert DATA_AXIS in MESH_AXES
    for axis, meaning in MESH_AXES.items():
        assert isinstance(meaning, str) and len(meaning) > 10, axis


def test_map_at_reduce_at_matches_numpy(mesh8):
    x = np.arange(64, dtype=np.float32).reshape(16, 4)
    out = map_reduce_at(
        mesh8,
        lambda xs: {"sum": xs.sum(axis=0), "sq": (xs * xs).sum()},
        in_specs=(P(DATA_AXIS, None),),
        jit=True,
    )(x)
    np.testing.assert_array_equal(np.asarray(out["sum"]), x.sum(axis=0))
    assert float(out["sq"]) == float((x * x).sum())


def test_map_at_row_sharded_output(mesh8):
    x = np.ones((16, 3), np.float32)
    fn = map_at(
        mesh8,
        lambda xs: xs * 2.0,
        in_specs=(P(DATA_AXIS, None),),
        out_specs=P(DATA_AXIS, None),
    )
    out = fn(jax.device_put(x, data_sharding(mesh8, 2)))
    np.testing.assert_array_equal(np.asarray(out), x * 2.0)


def test_sharded_jit_honors_annotations(mesh8):
    fn = sharded_jit(
        lambda x: x + 1.0,
        in_shardings=(data_sharding(mesh8, 2),),
        out_shardings=data_sharding(mesh8, 2),
    )
    out = fn(np.zeros((16, 2), np.float32))
    np.testing.assert_array_equal(np.asarray(out), np.ones((16, 2)))


def test_collective_wire_bytes_model():
    assert collective_wire_bytes(1, 1000) == 0  # one device moves nothing
    assert collective_wire_bytes(2, 1000) == 2000
    assert collective_wire_bytes(8, 1000) == 14000
    assert payload_nbytes({"a": np.zeros(4, np.float64)}) == 32


# ---------------------------------------------------------------------------
# equivalence matrix — the five call sites + the fused serve program
# ---------------------------------------------------------------------------


def _agg_over(size, x):
    """One tree_aggregate (sum + gram) over a mesh of ``size``."""
    mesh = default_mesh(size)

    def moments(xs, w):
        xw = xs * w[:, None]
        return {"sum": xw.sum(axis=0), "gram": xw.T @ xs}

    agg = make_tree_aggregate(moments, mesh)
    out = agg(*shard_batch(mesh, x))
    return {k: np.asarray(v) for k, v in out.items()}


def test_tree_aggregate_bitwise_f64_across_mesh_sizes():
    """f64 + integer-valued rows: the psum tree is EXACT, so every mesh
    size must agree bit for bit (jax.enable_x64 scopes the f64 leg to
    this test)."""
    rng = np.random.default_rng(7)
    x = rng.integers(-50, 50, size=(512, 6)).astype(np.float64)
    with jax.enable_x64(True):
        results = {s: _agg_over(s, x) for s in MESH_SIZES}
    base = results[1]
    assert base["sum"].dtype == np.float64
    np.testing.assert_array_equal(base["sum"], x.sum(axis=0))
    for s in MESH_SIZES[1:]:
        for k in base:
            np.testing.assert_array_equal(
                base[k], results[s][k],
                err_msg=f"mesh {s} leaf {k} not bitwise-equal to mesh 1",
            )


def test_tree_aggregate_f32_pinned_tolerance():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(512, 6)).astype(np.float32)
    results = {s: _agg_over(s, x) for s in MESH_SIZES}
    for s in MESH_SIZES[1:]:
        for k in results[1]:
            np.testing.assert_allclose(
                results[1][k], results[s][k], rtol=1e-5, atol=1e-5,
            )


def _blobs(seed=0, n=960, k=3, d=4, scale=8.0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)) * scale
    y = rng.integers(0, k, size=n)
    X = (centers[y] + rng.normal(size=(n, d))).astype(np.float32)
    return X, y


def test_kmeans_equivalence_across_mesh_sizes():
    from sntc_tpu.models import KMeans

    X, _ = _blobs()
    f = Frame({"features": X})
    fits = {
        s: KMeans(mesh=default_mesh(s), k=3, seed=1, maxIter=15).fit(f)
        for s in MESH_SIZES
    }
    base = np.asarray(fits[1].clusterCenters, np.float64)
    base_pred = np.asarray(fits[1].transform(f)["prediction"])
    for s in MESH_SIZES[1:]:
        np.testing.assert_allclose(
            np.asarray(fits[s].clusterCenters, np.float64), base,
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_array_equal(
            np.asarray(fits[s].transform(f)["prediction"]), base_pred
        )


def test_lda_e_step_equivalence_across_mesh_sizes():
    from sntc_tpu.models.lda import _run_e_step

    rng = np.random.default_rng(5)
    counts = rng.integers(0, 6, size=(64, 40)).astype(np.float32)
    k = 5
    eeb = np.exp(rng.normal(size=(k, 40)).astype(np.float32) * 0.1)
    key = jax.random.PRNGKey(0)
    outs = {
        s: _run_e_step(default_mesh(s), counts, eeb, 0.1, key, 20)
        for s in MESH_SIZES
    }
    g1, s1 = (np.asarray(a) for a in outs[1])
    for s in MESH_SIZES[1:]:
        g, st = (np.asarray(a) for a in outs[s])
        np.testing.assert_allclose(st, s1, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(g, g1, rtol=1e-5, atol=1e-4)


def test_pic_equivalence_across_mesh_sizes():
    from sntc_tpu.models import PowerIterationClustering

    rng = np.random.default_rng(2)
    n = 40
    src, dst, w = [], [], []
    for i in range(n):
        for j in range(i + 1, n):
            same = (i < n // 2) == (j < n // 2)
            if rng.random() < (0.8 if same else 0.05):
                src.append(i)
                dst.append(j)
                w.append(1.0 if same else 0.1)
    f = Frame({
        "src": np.array(src, np.int64), "dst": np.array(dst, np.int64),
        "weight": np.array(w, np.float64),
    })
    labels = {}
    for s in MESH_SIZES:
        out = PowerIterationClustering(
            mesh=default_mesh(s), k=2, maxIter=25, weightCol="weight",
            seed=1,
        ).assignClusters(f)
        order = np.argsort(np.asarray(out["id"]))
        labels[s] = np.asarray(out["cluster"])[order]
    for s in MESH_SIZES[1:]:
        a, b = labels[1], labels[s]
        # identical partition, cluster ids may swap
        assert (
            np.array_equal(a, b) or np.array_equal(a, 1 - b)
        ), f"mesh {s} partition differs from mesh 1"


def test_tree_histogram_equivalence_across_mesh_sizes():
    from sntc_tpu.models import DecisionTreeClassifier

    X, _ = _blobs(seed=4)
    y = (X[:, 0] > X[:, 0].mean()).astype(np.float64)
    f = Frame({"features": X, "label": y})
    preds = {}
    for s in MESH_SIZES:
        m = DecisionTreeClassifier(
            mesh=default_mesh(s), maxDepth=3, seed=1
        ).fit(f)
        preds[s] = np.asarray(m.transform(f)["prediction"])
    for s in MESH_SIZES[1:]:
        np.testing.assert_array_equal(preds[1], preds[s])
    assert float((preds[1] == y).mean()) > 0.9


def test_fused_lr_serve_equivalence_serve_mesh(mesh8, monkeypatch):
    """The fused serve program answers identically with and without a
    serve mesh (shard the dispatch rows over 8 devices vs single-device
    placement) — predictions bitwise, probabilities ≤1e-5."""
    from sntc_tpu.core.base import Pipeline
    from sntc_tpu.feature import StandardScaler
    from sntc_tpu.models import LogisticRegression
    from sntc_tpu.parallel.context import reset_serve_mesh, set_serve_mesh
    from sntc_tpu.serve.fuse import compile_serving

    monkeypatch.setenv("SNTC_SERVE_HOST_ROWS", "0")  # force device path
    rng = np.random.default_rng(0)
    X = rng.normal(3.0, 2.0, size=(1024, 6)).astype(np.float32)
    y = (X[:, 0] > 3.0).astype(np.float64)
    f = Frame({"features": X, "label": y})
    pm = Pipeline(stages=[
        StandardScaler(mesh=mesh8, inputCol="features",
                       outputCol="scaled", withMean=True),
        LogisticRegression(mesh=mesh8, featuresCol="scaled", maxIter=30),
    ]).fit(f)
    fused = compile_serving(pm)
    try:
        set_serve_mesh(None)
        single = fused.transform(f)
        set_serve_mesh(default_mesh(8))
        sharded = fused.transform(f)
    finally:
        reset_serve_mesh()
    np.testing.assert_array_equal(
        np.asarray(single["prediction"]), np.asarray(sharded["prediction"])
    )
    np.testing.assert_allclose(
        np.asarray(single["probability"]),
        np.asarray(sharded["probability"]), rtol=1e-5, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# elastic resize / OOM split
# ---------------------------------------------------------------------------


def _int_batch(n=512, d=6, seed=11):
    rng = np.random.default_rng(seed)
    return rng.integers(-20, 20, size=(n, d)).astype(np.float32)


def _sum_fn(xs, w):
    xw = xs * w[:, None]
    return {"sum": xw.sum(axis=0), "gram": xw.T @ xs}


def test_device_lost_resizes_mesh_and_result_is_bitwise(mesh8):
    from sntc_tpu.resilience.device import DeviceFaultDomain

    x = _int_batch()
    baseline = make_tree_aggregate(_sum_fn, mesh8)(*shard_batch(mesh8, x))
    dom = DeviceFaultDomain(probe_async=False)
    set_collective_domain(dom)
    agg = make_tree_aggregate(_sum_fn, mesh8)
    before = _get("sntc_collective_resizes_total")
    R.arm("collective.dispatch", kind="device_lost", times=1)
    out = agg(*shard_batch(mesh8, x))
    assert int(agg.mesh().shape[DATA_AXIS]) == 4  # 8 -> shrink to 4
    for k in ("sum", "gram"):
        np.testing.assert_array_equal(
            np.asarray(out[k]), np.asarray(baseline[k])
        )
    assert _get("sntc_collective_resizes_total") == before + 1
    assert _get("sntc_collective_mesh_devices", axis=DATA_AXIS) == 4
    decisions = [r.get("decision") for r in dom.journal]
    assert "mesh_resize" in decisions
    assert not dom.host_degraded
    # a batch sharded for the ORIGINAL mesh still dispatches (lazy
    # migration onto the survivors)
    out2 = agg(*shard_batch(mesh8, x))
    np.testing.assert_array_equal(
        np.asarray(out2["sum"]), np.asarray(baseline["sum"])
    )


def test_resize_disabled_env_propagates(mesh8, monkeypatch):
    monkeypatch.setenv("SNTC_MESH_RESIZE", "0")
    agg = make_tree_aggregate(_sum_fn, mesh8)
    x = _int_batch(n=64)
    R.arm("collective.dispatch", kind="device_lost", times=1)
    with pytest.raises(Exception) as ei:
        agg(*shard_batch(mesh8, x))
    assert "device" in str(ei.value).lower()


def test_single_device_mesh_never_resizes():
    mesh1 = default_mesh(1)
    agg = make_tree_aggregate(_sum_fn, mesh1)
    x = _int_batch(n=64)
    R.arm("collective.dispatch", kind="device_lost", times=1)
    with pytest.raises(Exception):
        agg(*shard_batch(mesh1, x))


def test_device_oom_splits_and_sums_bitwise(mesh8):
    from sntc_tpu.resilience.device import DeviceFaultDomain

    x = _int_batch(seed=13)
    baseline = make_tree_aggregate(_sum_fn, mesh8)(*shard_batch(mesh8, x))
    dom = DeviceFaultDomain(probe_async=False)
    set_collective_domain(dom)
    agg = make_tree_aggregate(_sum_fn, mesh8)
    R.arm("collective.dispatch", kind="device_oom", times=1)
    out = agg(*shard_batch(mesh8, x))
    for k in ("sum", "gram"):
        np.testing.assert_array_equal(
            np.asarray(out[k]), np.asarray(baseline[k])
        )
    assert dom.oom_splits == 1
    assert int(agg.mesh().shape[DATA_AXIS]) == 8  # no resize on OOM


def test_resize_mid_fit_converges_with_survivors(mesh8):
    """The chaos claim in miniature: a participant dies mid-ALS-fit
    (the one estimator whose loop dispatches the aggregate per
    iteration — LR/LinReg run their whole optimizer inside one XLA
    program), the fit resizes onto the survivors and still converges;
    the decision is journaled, the host never degrades."""
    from sntc_tpu.models import ALS
    from sntc_tpu.resilience.device import DeviceFaultDomain

    rng = np.random.default_rng(0)
    n_u, n_i, rank = 40, 30, 3
    U = rng.normal(size=(n_u, rank)) / np.sqrt(rank)
    V = rng.normal(size=(n_i, rank)) / np.sqrt(rank)
    full = U @ V.T + 2.0
    mask = rng.random((n_u, n_i)) < 0.6
    uu, ii = np.nonzero(mask)
    f = Frame({
        "user": uu.astype(np.int64), "item": ii.astype(np.int64),
        "rating": full[uu, ii].astype(np.float32),
    })
    dom = DeviceFaultDomain(probe_async=False)
    set_collective_domain(dom)
    # fire mid-fit: let the first iteration's dispatches succeed first
    R.arm("collective.dispatch", kind="device_lost", after=3, times=1)
    m = ALS(
        mesh=mesh8, rank=4, maxIter=10, regParam=0.02, seed=2
    ).fit(f)
    pred = np.asarray(
        m.transform(Frame({"user": uu, "item": ii}))["prediction"]
    )
    rmse = float(np.sqrt(np.mean((pred - full[uu, ii]) ** 2)))
    assert rmse < 0.1, rmse  # noiseless low-rank: survivors converged
    decisions = [r.get("decision") for r in dom.journal]
    assert "mesh_resize" in decisions
    assert not dom.host_degraded


# ---------------------------------------------------------------------------
# transfer-ledger attribution (satellite bugfix regression)
# ---------------------------------------------------------------------------


def test_shard_placement_lands_in_transfer_ledger(mesh8):
    from sntc_tpu.utils.profiling import TransferLedger, ledger_scope

    led = TransferLedger()
    x = np.random.default_rng(1).normal(size=(256, 4)).astype(np.float32)
    with ledger_scope(led):
        shard_batch(mesh8, x)
    snap = led.snapshot()
    # the batch array + the weights column both crossed the host link
    assert snap["uploads"] >= 2, snap
    assert snap["upload_bytes"] >= x.nbytes, snap
    # movement is NOT a fused dispatch — the dispatch series keeps
    # meaning "fused program calls"
    assert snap["dispatches"] == 0, snap


def test_resize_replacement_attributed_to_ledger(mesh8):
    from sntc_tpu.utils.profiling import TransferLedger, ledger_scope

    led = TransferLedger()
    x = _int_batch(n=128, seed=17)
    agg = make_tree_aggregate(_sum_fn, mesh8)
    with ledger_scope(led):
        args = shard_batch(mesh8, x)
        placed = led.snapshot()["upload_bytes"]
        R.arm("collective.dispatch", kind="device_lost", times=1)
        agg(*args)
    snap = led.snapshot()
    # the resize re-placed the batch on the survivors: strictly more
    # bytes than the initial placement, still zero dispatches
    assert snap["upload_bytes"] > placed, snap
    assert snap["dispatches"] == 0, snap


# ---------------------------------------------------------------------------
# evidence metrics
# ---------------------------------------------------------------------------


def test_collective_dispatch_metrics(mesh8):
    x = np.ones((64, 3), np.float32)
    d0 = _get("sntc_collective_dispatches_total",
              op="tree_aggregate", axis=DATA_AXIS)
    b0 = _get("sntc_collective_bytes_moved_total",
              op="tree_aggregate", axis=DATA_AXIS)
    agg = make_tree_aggregate(
        lambda xs, w: (xs * w[:, None]).sum(axis=0), mesh8
    )
    out = agg(*shard_batch(mesh8, x))
    assert _get("sntc_collective_dispatches_total",
                op="tree_aggregate", axis=DATA_AXIS) == d0 + 1
    wire = collective_wire_bytes(8, int(out.nbytes))
    assert _get("sntc_collective_bytes_moved_total",
                op="tree_aggregate", axis=DATA_AXIS) == b0 + wire
    assert _get("sntc_collective_mesh_devices", axis=DATA_AXIS) == 8


def test_model_op_metrics_emitted(mesh8):
    from sntc_tpu.models import KMeans

    X, _ = _blobs(seed=9, n=256)
    d0 = _get("sntc_collective_dispatches_total",
              op="kmeans.lloyd", axis=DATA_AXIS)
    KMeans(mesh=mesh8, k=2, seed=1, maxIter=5).fit(Frame({"features": X}))
    assert _get("sntc_collective_dispatches_total",
                op="kmeans.lloyd", axis=DATA_AXIS) > d0


# ---------------------------------------------------------------------------
# drift check wiring
# ---------------------------------------------------------------------------


def test_mesh_axes_consistent_code_registry_docs():
    checker = _load_script("check_mesh_axes")
    assert checker.check() == []
