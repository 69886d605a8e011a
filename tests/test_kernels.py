"""Kernel forge (r21): twin-equality matrix, poison ladder, drift check.

The serving kernel tier (``sntc_tpu/kernels/``) promises each Pallas
kernel is interchangeable with its lowered-jnp twin — bitwise in f64,
<=1e-5 rel in f32 (the registered tolerances; the traversal and pad
kernels are in fact bit-exact in both, by construction).  Tier-1 runs
the whole matrix in interpret mode on CPU:

* ``forest_traversal`` vs ``grower.forest_leaf_stats`` on random
  forests across depths/widths/stat shapes;
* rf/gbt/dt heads end-to-end through ``BatchPredictor`` — kernel tier
  vs kernels-off — across shape buckets and row-validity masks;
* ``pad_assemble`` vs ``Frame.pad_rows(...).with_column(VALID_COL)``;
* a forced ``kernel.compile`` fault proving the poison ladder serves
  bitwise on the XLA path with zero quarantines/strikes, host-level
  AND inside a fused trace (where the segment must recompile on pure
  XLA, not fall to the eager host path);
* ``tree_hist`` selection: the table of ``tree_hist_impl``;
* the registry ⇔ docs ⇔ tests drift check
  (``scripts/check_kernel_registry.py``) wired tier-1.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sntc_tpu.resilience.faults as R
from sntc_tpu.core.base import Pipeline, PipelineModel
from sntc_tpu.core.frame import Frame
from sntc_tpu.fuse import compile_serving, fused_segments, fusion_stats
from sntc_tpu.kernels.assemble import (
    _pad_column_np,
    pad_assemble,
    pad_fits_pallas,
    pad_rows_pallas,
)
from sntc_tpu.kernels.forest import (
    forest_fits_pallas,
    forest_leaf_stats_pallas,
)
from sntc_tpu.kernels.registry import (
    clear_poisons,
    kernel_stats,
    registered_kernels,
    resolve_impl,
    resolve_serve_kernels,
)
from sntc_tpu.models.tree.grower import forest_leaf_stats
from sntc_tpu.resilience.device import DeviceFaultDomain
from sntc_tpu.serve.transform import VALID_COL, BatchPredictor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _kernel_tier(monkeypatch):
    """Every test here runs the kernel tier in interpret mode with a
    clean poison ledger and disarmed faults."""
    monkeypatch.setenv("SNTC_SERVE_KERNELS", "interpret")
    clear_poisons()
    R.clear()
    yield
    R.clear()
    clear_poisons()


def _random_forest(rng, T, max_depth, F, S, dtype=np.float32):
    """A structurally valid random forest: internal nodes carry a
    feature/threshold, leaves carry stats, absent nodes are -2 (the
    grower's dense layout)."""
    M = 2 ** (max_depth + 1) - 1
    feat = np.full((T, M), -2, np.int32)
    thr = np.zeros((T, M), dtype)
    leaf = np.zeros((T, M, S), dtype)

    def build(t, node, depth):
        if depth < max_depth and rng.random() < 0.7:
            feat[t, node] = rng.integers(0, F)
            thr[t, node] = rng.normal()
            build(t, 2 * node + 1, depth + 1)
            build(t, 2 * node + 2, depth + 1)
        else:
            feat[t, node] = -1
            leaf[t, node] = rng.random(S).astype(dtype)

    for t in range(T):
        build(t, 0, 0)
    return feat, thr, leaf


@pytest.mark.parametrize(
    "T,N,F,S,max_depth",
    [
        (1, 5, 3, 2, 2),
        (3, 17, 7, 3, 4),
        (2, 128, 4, 5, 3),
        (4, 130, 6, 2, 5),
    ],
)
def test_forest_traversal_matches_twin_f32(T, N, F, S, max_depth):
    rng = np.random.default_rng(T * 1000 + N)
    feat, thr, leaf = _random_forest(rng, T, max_depth, F, S)
    X = rng.normal(size=(N, F)).astype(np.float32)
    ref = np.asarray(
        forest_leaf_stats(
            jnp.asarray(X), jnp.asarray(feat), jnp.asarray(thr),
            jnp.asarray(leaf), max_depth=max_depth,
        )
    )
    out = np.asarray(
        forest_leaf_stats_pallas(
            jnp.asarray(X), jnp.asarray(feat), jnp.asarray(thr),
            jnp.asarray(leaf), max_depth=max_depth, interpret=True,
        )
    )
    # documented tolerance <=1e-5 rel; the kernel is actually bit-exact
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=0)
    np.testing.assert_array_equal(out, ref)


def test_forest_traversal_matches_twin_f64_bitwise():
    with jax.enable_x64(True):
        rng = np.random.default_rng(7)
        feat, thr, leaf = _random_forest(rng, 3, 4, 5, 3, np.float64)
        X = rng.normal(size=(23, 5))
        ref = np.asarray(
            forest_leaf_stats(
                jnp.asarray(X), jnp.asarray(feat), jnp.asarray(thr),
                jnp.asarray(leaf), max_depth=4,
            )
        )
        out = np.asarray(
            forest_leaf_stats_pallas(
                jnp.asarray(X), jnp.asarray(feat), jnp.asarray(thr),
                jnp.asarray(leaf), max_depth=4, interpret=True,
            )
        )
    assert ref.dtype == np.float64
    np.testing.assert_array_equal(out, ref)


def test_pad_rows_kernel_bitwise():
    rng = np.random.default_rng(3)
    for n, c, target in [(5, 3, 8), (6, 1, 16), (130, 4, 256)]:
        a = rng.normal(size=(n, c)).astype(np.float32)
        out = np.asarray(
            pad_rows_pallas(jnp.asarray(a), target=target, interpret=True)
        )
        np.testing.assert_array_equal(out, _pad_column_np(a, target))


def test_pad_assemble_matches_frame_twin_all_dtypes():
    rng = np.random.default_rng(4)
    f = Frame({
        "x": rng.normal(size=(5, 4)).astype(np.float32),
        "y": rng.normal(size=5),  # f64: numpy twin without x64
        "i": np.arange(5),
        "s": np.array(list("abcde"), dtype=object),
    })
    valid = np.zeros(8, bool)
    valid[:5] = True
    out = pad_assemble(f, 8, valid)
    ref = f.pad_rows(8).with_column(VALID_COL, valid)
    assert out.columns == ref.columns
    for c in ref.columns:
        np.testing.assert_array_equal(np.asarray(out[c]), np.asarray(ref[c]))
        assert out[c].dtype == ref[c].dtype


def _head_pipeline(kind, rng):
    from sntc_tpu.feature import DCT, VectorAssembler
    from sntc_tpu.models.tree.decision_tree import DecisionTreeClassifier
    from sntc_tpu.models.tree.gbt import GBTClassifier
    from sntc_tpu.models.tree.random_forest import RandomForestClassifier

    D = 4
    X = np.abs(rng.normal(3.0, 2.0, size=(120, D))).astype(np.float32)
    cols = {f"c{i}": X[:, i].copy() for i in range(D)}
    cols["label"] = (X[:, 0] > 3.0).astype(np.float64)
    train = Frame(cols)
    head = {
        "rf": lambda: RandomForestClassifier(
            numTrees=3, maxDepth=3, seed=7, featuresCol="dct"
        ),
        "gbt": lambda: GBTClassifier(maxIter=3, maxDepth=2, featuresCol="dct"),
        "dt": lambda: DecisionTreeClassifier(maxDepth=3, featuresCol="dct"),
    }[kind]()
    pm = Pipeline(stages=[
        VectorAssembler(
            inputCols=[f"c{i}" for i in range(D)], outputCol="features"
        ),
        DCT(inputCol="features", outputCol="dct"),
        head,
    ]).fit(train)
    return pm, train.drop("label")


_SCORE_COLS = ("rawPrediction", "probability", "prediction")


@pytest.mark.parametrize("kind", ["rf", "gbt", "dt"])
@pytest.mark.parametrize("rows,mask", [
    (13, None),      # padded bucket
    (16, None),      # exact bucket
    (11, "partial"),  # row-validity mask + pad
])
def test_heads_kernel_tier_matches_xla(kind, rows, mask, monkeypatch):
    """The equality matrix: rf/gbt/dt heads × shape buckets ×
    row-validity masks, kernel tier (interpret) vs kernels-off, through
    the full fused BatchPredictor path."""
    rng = np.random.default_rng(11)
    pm, serve = _head_pipeline(kind, rng)
    frame = serve.slice(0, rows)
    row_valid = None
    if mask == "partial":
        row_valid = np.ones(rows, dtype=bool)
        row_valid[::3] = False

    monkeypatch.setenv("SNTC_SERVE_KERNELS", "off")
    ref = BatchPredictor(
        compile_serving(pm), bucket_rows=16
    ).predict_frame(frame, row_valid=row_valid)

    monkeypatch.setenv("SNTC_SERVE_KERNELS", "interpret")
    fused = compile_serving(pm)
    out = BatchPredictor(fused, bucket_rows=16).predict_frame(
        frame, row_valid=row_valid
    )
    for c in _SCORE_COLS:
        np.testing.assert_array_equal(
            np.asarray(out[c]), np.asarray(ref[c]), err_msg=f"{kind}:{c}"
        )
    assert fusion_stats(fused)["kernels"]["poisoned_signatures"] == 0


def test_host_level_kernel_compile_fault_serves_twin_bitwise():
    """Unfused head: an injected kernel.compile compile_error poisons
    exactly that (kernel, signature) and the batch serves on the XLA
    twin — bitwise, no exception, nothing reaches any fault domain."""
    from sntc_tpu.models.tree.random_forest import RandomForestClassifier

    rng = np.random.default_rng(0)
    X = rng.normal(size=(80, 5)).astype(np.float64)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
    model = RandomForestClassifier(numTrees=3, maxDepth=3, seed=7).fit(
        Frame({"features": X, "label": y})
    )
    Xs = rng.normal(size=(33, 5)).astype(np.float32)
    R.arm("kernel.compile", kind="compile_error", times=1)
    out = np.asarray(model._predict_all_dev(Xs))
    R.clear()
    os.environ["SNTC_SERVE_KERNELS"] = "off"
    ref = np.asarray(model._predict_all_dev(Xs))
    np.testing.assert_array_equal(out, ref)
    st = kernel_stats()
    assert st["poisoned_signatures"] == 1
    reason = next(iter(st["poisoned"].values()))
    assert "kernel.compile" in reason
    # poisoned signature stays on the twin with the tier back on
    os.environ["SNTC_SERVE_KERNELS"] = "interpret"
    np.testing.assert_array_equal(
        np.asarray(model._predict_all_dev(Xs)), ref
    )


def test_forced_pallas_on_cpu_raises():
    """``SNTC_SERVE_KERNELS=pallas`` asked for by name on a backend
    that cannot compile the kernel: the lowering failure raises —
    nothing is poisoned and no twin is served behind the caller's
    back (``auto`` keeps the poison ladder)."""
    from sntc_tpu.models.tree.random_forest import RandomForestClassifier

    rng = np.random.default_rng(3)
    X = rng.normal(size=(64, 5)).astype(np.float64)
    y = (X[:, 0] - X[:, 2] > 0).astype(np.float64)
    model = RandomForestClassifier(numTrees=3, maxDepth=3, seed=5).fit(
        Frame({"features": X, "label": y})
    )
    Xs = rng.normal(size=(21, 5)).astype(np.float32)
    os.environ["SNTC_SERVE_KERNELS"] = "pallas"
    with pytest.raises(ValueError, match="(?i)interpret mode"):
        model._predict_all_dev(Xs)
    assert kernel_stats()["poisoned_signatures"] == 0


def test_forced_pallas_in_fused_trace_raises():
    """Same contract inside a fused segment: the enclosing compile's
    kernel failure propagates instead of retracing on the twin."""
    rng = np.random.default_rng(12)
    pm, serve = _head_pipeline("rf", rng)
    os.environ["SNTC_SERVE_KERNELS"] = "pallas"
    bp = BatchPredictor(
        compile_serving(pm), bucket_rows=16,
        device_domain=DeviceFaultDomain(),
    )
    with pytest.raises(Exception, match="(?i)interpret mode"):
        bp.predict_frame(serve.slice(0, 13))
    assert kernel_stats()["poisoned_signatures"] == 0


def test_every_registered_kernel_lowers_for_tpu():
    """Cross-lower every registered kernel through the Pallas TPU
    lowering at the shapes ``chip_smoke.py`` serves (``smoke_case``):
    block-shape and layout rules the interpreter never applies — e.g. a
    ``(1, Mp)`` block over ``[T, Mp]`` for T > 1 — fail here, on CPU,
    instead of being poisoned onto the twin on the chip."""
    import functools

    for name, spec in registered_kernels().items():
        assert spec.smoke_case is not None, name
        kernel_fn, _twin, args, _rtol = spec.smoke_case(2048)
        shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
        jax.jit(functools.partial(kernel_fn, interpret=False)).trace(
            *shapes
        ).lower(lowering_platforms=("tpu",))


def test_pad_assemble_nonfinite_column_pads_on_host():
    """An Inf in a float32 column must not ride the one-hot matmul
    (0 * inf = NaN would poison every padded row of the column)."""
    f = Frame({"x": np.array([1.0, np.inf, 3.0], np.float32)})
    valid = np.array([True] * 3 + [False] * 5)
    out = pad_assemble(f, 8, valid)
    np.testing.assert_array_equal(
        np.asarray(out["x"]), np.asarray(f.pad_rows(8)["x"])
    )


def test_classify_kernel_error_scope():
    """The widened classifier recognizes Pallas/Mosaic lowering text
    and chained causes, defers to the strict device classifier for
    XLA-shaped errors, and stays None for arbitrary user errors."""
    from sntc_tpu.kernels.registry import classify_kernel_error

    assert classify_kernel_error(
        ValueError("Only interpret mode is supported on CPU backend.")
    ) == "compile_error"
    wrapped = RuntimeError("fused trace failed")
    wrapped.__cause__ = ValueError("Mosaic lowering failed: op")
    assert classify_kernel_error(wrapped) == "compile_error"
    assert classify_kernel_error(ValueError("bad user regex")) is None
    assert classify_kernel_error(None) is None


def test_fused_kernel_compile_fault_recompiles_on_xla_path():
    """Inside a fused trace: the kernel poisons, the SEGMENT survives —
    it recompiles the same signature on pure XLA (zero eager fallbacks,
    zero segment poisons, zero domain faults, zero quarantines) and the
    sink-visible outputs stay bitwise vs an unfaulted reference."""
    rng = np.random.default_rng(11)
    pm, serve = _head_pipeline("rf", rng)
    frame = serve.slice(0, 13)

    os.environ["SNTC_SERVE_KERNELS"] = "off"
    ref = BatchPredictor(
        compile_serving(pm), bucket_rows=16
    ).predict_frame(frame)

    os.environ["SNTC_SERVE_KERNELS"] = "interpret"
    fused = compile_serving(pm)
    dom = DeviceFaultDomain()
    bp = BatchPredictor(fused, bucket_rows=16, device_domain=dom)
    R.arm("kernel.compile", kind="compile_error", times=1)
    out = bp.predict_frame(frame)
    R.clear()
    for c in _SCORE_COLS:
        np.testing.assert_array_equal(np.asarray(out[c]), np.asarray(ref[c]))
    fs = fusion_stats(fused)
    assert fs["fallbacks"] == 0  # XLA path, NOT the eager host path
    assert fs["poisoned_signatures"] == 0  # the segment is not poisoned
    assert fs["kernels"]["poisoned_signatures"] >= 1
    assert dom.fault_count() == 0  # platform fault, zero strikes
    assert dom.stats()["state"] == "DEVICE_OK"
    seg = fused_segments(fused)[0]
    assert seg.poisoned_served == 0


def test_registry_selection_and_guards(monkeypatch):
    assert set(registered_kernels()) >= {
        "forest_traversal", "pad_assemble", "tree_hist",
    }
    monkeypatch.setenv("SNTC_SERVE_KERNELS", "off")
    assert resolve_serve_kernels() == "off"
    assert resolve_impl(
        "forest_traversal", n_nodes=7, n_features=3, n_stats=2
    ) == "xla"
    monkeypatch.setenv("SNTC_SERVE_KERNELS", "interpret")
    assert resolve_impl(
        "forest_traversal", n_nodes=7, n_features=3, n_stats=2
    ) == "interpret"
    # guard reject: a freak-width forest falls back to the XLA walk
    assert not forest_fits_pallas(1 << 22, 4, 2)
    assert resolve_impl(
        "forest_traversal", n_nodes=1 << 22, n_features=4, n_stats=2
    ) == "xla"
    assert pad_fits_pallas(64, 8)
    assert not pad_fits_pallas(1 << 20, 1 << 10)


@pytest.mark.parametrize("backend,env,on_the_kernel", [
    ("cpu", None, False),  # the default off a TPU: the XLA segment_sum
    ("tpu", None, True),  # the default on the chip
    ("cpu", "pallas", True),  # asked for by name: the rehearsal, the twins
    ("tpu", "segment", False),  # the operator's way back to the twin
])
def test_tree_hist_impl_table(monkeypatch, backend, env, on_the_kernel):
    """The one selection function of the fit's histogram: backend x
    ``SNTC_TREE_HIST`` x mesh x width -> implementation, and the counter
    each verdict bumps (``tree_hist_roofline`` reads the first)."""
    from sntc_tpu.obs.metrics import registry
    from sntc_tpu.ops.pallas_histogram import tree_hist_impl

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if env is None:
        monkeypatch.delenv("SNTC_TREE_HIST", raising=False)
    else:
        monkeypatch.setenv("SNTC_TREE_HIST", env)

    def counts():
        return (
            registry().get("sntc_kernel_dispatch_total",
                           kernel="tree_hist", impl="pallas") or 0,
            registry().get("sntc_kernel_fallback_total",
                           kernel="tree_hist", reason="segment") or 0,
        )

    # (nodes, bins, mesh): the cell's deepest level, the guard's edge on
    # both sides, a freak width, and no mesh to map the kernel over
    for n_nodes, n_bins, mesh, admitted in [
        (8, 32, object(), True),
        (128, 32, object(), True),
        (256, 32, object(), False),
        (1 << 14, 128, object(), False),
        (8, 32, None, False),
    ]:
        want = "pallas" if on_the_kernel and admitted else "segment"
        dispatched, fell_back = counts()
        assert tree_hist_impl(n_nodes, n_bins, mesh) == want
        assert counts() == (
            dispatched + (want == "pallas"), fell_back + (want == "segment")
        )


def test_probed_peaks_sources(monkeypatch):
    from sntc_tpu.obs.cost import probed_peaks

    monkeypatch.delenv("SNTC_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("SNTC_PEAK_BW", raising=False)
    cpu = probed_peaks("cpu")
    assert cpu["peak_source"] == "estimate"  # honest CPU labeling
    assert probed_peaks() == cpu  # default: this process's device_kind
    tpu = probed_peaks("TPU v5 lite")
    assert tpu["peak_source"] == "datasheet"
    assert tpu["flops"] > cpu["flops"]
    # keyed on device_kind: an unknown chip is an error, never the CPU
    # row (or another chip's) standing in
    with pytest.raises(KeyError, match="TPU v9"):
        probed_peaks("TPU v9")
    monkeypatch.setenv("SNTC_PEAK_FLOPS", "1e12")
    over = probed_peaks("cpu")
    assert over["flops"] == 1e12 and over["peak_source"] == "env"


def test_roofline_math():
    from sntc_tpu.obs.cost import roofline

    r = roofline(
        {"flops": 1e9, "bytes accessed": 1e8},
        seconds=2.0, invocations=4, device_kind="cpu",
    )
    assert r["arithmetic_intensity"] == pytest.approx(10.0)
    assert r["achieved_flops"] == pytest.approx(2e9)
    assert r["mfu"] == pytest.approx(2e9 / r["peak_flops"])
    assert r["peak_source"] == "estimate"
    assert roofline(None) is None
    warm = roofline({"flops": 1e9}, seconds=0.0, invocations=0)
    assert "mfu" not in warm and warm["flops"] == 1e9


# ---------------------------------------------------------------------------
# kernel-registry drift check (tier-1 wiring of check_kernel_registry)
# ---------------------------------------------------------------------------


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_registry_consistent_code_docs_tests():
    checker = _load_script("check_kernel_registry")
    assert checker.check() == []
