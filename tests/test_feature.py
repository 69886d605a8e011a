import numpy as np
import pytest

from sntc_tpu.core.base import Estimator, Model, Pipeline
from sntc_tpu.core.frame import Frame
from sntc_tpu.feature import (
    ChiSqSelector,
    IndexToString,
    StandardScaler,
    StringIndexer,
    StringIndexerModel,
    VectorAssembler,
)


# ---------------- StringIndexer ----------------

def _label_frame():
    labels = ["b"] * 5 + ["a"] * 5 + ["c"] * 3 + ["d"] * 1
    return Frame({"label": np.array(labels, dtype=object)})


def test_string_indexer_frequency_desc_tiebreak_alpha():
    # b and a tie at 5 -> alphabetical ascending breaks the tie (Spark parity)
    model = StringIndexer(inputCol="label", outputCol="idx").fit(_label_frame())
    assert model.labels == ["a", "b", "c", "d"]
    out = model.transform(_label_frame())
    assert out["idx"].dtype == np.float64
    assert out["idx"][0] == 1.0  # "b"


def test_string_indexer_order_types():
    f = _label_frame()
    assert StringIndexer(stringOrderType="alphabetAsc").fit(f).labels == ["a", "b", "c", "d"]
    assert StringIndexer(stringOrderType="alphabetDesc").fit(f).labels == ["d", "c", "b", "a"]
    assert StringIndexer(stringOrderType="frequencyAsc").fit(f).labels == ["d", "c", "a", "b"]


def test_string_indexer_handle_invalid():
    model = StringIndexer(inputCol="label", outputCol="idx").fit(_label_frame())
    unseen = Frame({"label": np.array(["a", "zz"], dtype=object)})
    with pytest.raises(ValueError, match="unseen"):
        model.transform(unseen)
    skipped = model.copy({"handleInvalid": "skip"}).transform(unseen)
    assert skipped.num_rows == 1
    kept = model.copy({"handleInvalid": "keep"}).transform(unseen)
    assert kept["idx"].tolist() == [0.0, 4.0]


def test_index_to_string_roundtrip():
    f = _label_frame()
    model = StringIndexer(inputCol="label", outputCol="idx").fit(f)
    out = model.transform(f)
    back = IndexToString(inputCol="idx", outputCol="orig", labels=model.labels).transform(out)
    assert list(back["orig"]) == list(f["label"])


# ---------------- StringIndexer inside a Pipeline fit ----------------


class _Recorder(Estimator):
    """A stub estimator that records what it is asked to do."""

    def __init__(self, log=None, **kwargs):
        super().__init__(**kwargs)
        self.log = [] if log is None else log

    def _fit(self, frame):
        self.log.append(("fit", self, frame))
        return _RecorderModel(self.log, self)


class _RecorderModel(Model):
    def __init__(self, log, owner, **kwargs):
        super().__init__(**kwargs)
        self.log, self.owner = log, owner

    def transform(self, frame):
        self.log.append(("transform", self.owner, frame))
        return frame.with_column("seen", np.ones(frame.num_rows))


def _index_passes(site):
    from sntc_tpu.obs import registry

    return registry().get("sntc_feature_index_passes_total", site=site) or 0


def _reference_index(values, order):
    """The indexer's contract by hand: a label is ``str()`` of a value,
    ordered by ``order`` with ties broken by the string ascending."""
    from collections import Counter

    counts = Counter(str(v) for v in values)
    key = {
        "frequencyDesc": lambda kv: (-kv[1], kv[0]),
        "frequencyAsc": lambda kv: (kv[1], kv[0]),
        "alphabetAsc": lambda kv: kv[0],
        "alphabetDesc": lambda kv: kv[0],
    }[order]
    labels = [l for l, _ in sorted(counts.items(), key=key,
                                   reverse=order == "alphabetDesc")]
    pos = {l: float(i) for i, l in enumerate(labels)}
    return labels, np.array([pos[str(v)] for v in values], np.float64)


_S = ["b", "a", "c", "b", "a", "d", "c", "e"]  # a, b, c tie at 2
_LABEL_COLUMNS = {
    "object": np.array(_S, dtype=object),
    "object_untied": np.array(_S + ["b", "b", "c"], dtype=object),
    "object_none": np.array(_S + [None, "a", None], dtype=object),
    "object_nan": np.array(_S + [np.nan, float("nan"), "e"], dtype=object),
    "object_none_nan": np.array(_S + [None, np.nan, None], dtype=object),
    "object_none_nan_and_str_none": np.array(
        _S + [None, "None", np.nan, "nan", None], dtype=object),
    "int64": np.array([3, 1, 2, 3, 1, 10, 2, 7], dtype=np.int64),
    "object_int": np.array([3, 1, 2, 3, 1, 10, 2, 7, None], dtype=object),
    "object_float": np.array([3.0, 1.5, 2.0, 3.0, 1.5, np.nan, 2.0, 7.25],
                             dtype=object),
    "float64": np.array([3.0, 1.5, 2.0, 3.0, 1.5, 10.0, 2.0, 7.25]),
    "float64_nan": np.array([3.0, np.nan, 2.0, 3.0, np.nan, 0.5, 2.0, 7.0]),
}


@pytest.mark.parametrize("multi", [False, True], ids=["single", "multi"])
@pytest.mark.parametrize("column", sorted(_LABEL_COLUMNS))
@pytest.mark.parametrize(
    "order", ["frequencyDesc", "frequencyAsc", "alphabetDesc", "alphabetAsc"]
)
def test_string_indexer_pipeline_fit_is_fit_then_transform(order, column,
                                                           multi):
    """A pipeline fit indexes its label column from one hash pass; the
    vocabulary and the column are those of ``fit`` then ``transform``."""
    values = _LABEL_COLUMNS[column]
    other = np.array(["x", "y", "x", "z", "y", "x", "z", "x"] * 2,
                     dtype=object)[: len(values)]
    f = Frame({"lab": values, "other": other, "v": np.arange(len(values))})
    if multi:
        cols = dict(inputCols=["lab", "other"], outputCols=["i0", "i1"])
        ins, outs = ["lab", "other"], ["i0", "i1"]
    else:
        cols = dict(inputCol="lab", outputCol="i0")
        ins, outs = ["lab"], ["i0"]
    separate = StringIndexer(stringOrderType=order, **cols).fit(f)
    expected = separate.transform(f)

    before = {s: _index_passes(s) for s in ("fit", "transform",
                                            "fit_transform")}
    log = []
    model = Pipeline(stages=[StringIndexer(stringOrderType=order, **cols),
                             _Recorder(log)]).fit(f)
    passes = {s: _index_passes(s) - n for s, n in before.items()}
    assert passes == {"fit": 0, "transform": 0, "fit_transform": len(ins)}

    fitted = model.getStages()[0]
    assert isinstance(fitted, StringIndexerModel)
    assert fitted.labelsArray == separate.labelsArray
    assert fitted.getStringOrderType() == order
    ((what, _, seen),) = log
    assert what == "fit"
    np.testing.assert_array_equal(seen["v"], f["v"])  # row order kept
    for c, o, labels in zip(ins, outs, fitted.labelsArray):
        ref_labels, ref_out = _reference_index(f[c], order)
        assert labels == ref_labels
        assert seen[o].dtype == np.float64
        np.testing.assert_array_equal(seen[o], expected[o])
        np.testing.assert_array_equal(seen[o], ref_out)


@pytest.mark.parametrize("mode", ["error", "skip", "keep"])
def test_string_indexer_pipeline_fit_under_handle_invalid(mode):
    """On the frame it was fitted on no label is unseen: every mode
    gives every row its index, as ``fit`` then ``transform`` does."""
    f = Frame({"lab": _LABEL_COLUMNS["object_none_nan_and_str_none"]})
    stage = StringIndexer(inputCol="lab", outputCol="i", handleInvalid=mode)
    expected = stage.fit(f).transform(f)
    log = []
    model = Pipeline(stages=[stage, _Recorder(log)]).fit(f)
    seen = log[0][2]
    assert seen.num_rows == f.num_rows
    np.testing.assert_array_equal(seen["i"], expected["i"])
    assert model.getStages()[0].getHandleInvalid() == mode
    # the fitted model still refuses, drops or keeps an unseen label
    unseen = Frame({"lab": np.array(["a", "zz"], dtype=object)})
    fitted = model.getStages()[0]
    if mode == "error":
        with pytest.raises(ValueError, match="unseen labels \\['zz'\\]"):
            fitted.transform(unseen)
    else:
        out = fitted.transform(unseen)["i"]
        n = float(len(fitted.labels))
        assert out.tolist() == ([fitted.labels.index("a")] if mode == "skip"
                                else [fitted.labels.index("a"), n])


@pytest.mark.parametrize("values", [
    np.array([1, 1.0, "1", True, 1, 2], dtype=object),
    np.array([0.0, -0.0, 0.0, 1.0]),
    np.array([0.0, -0.0, 0.0, 1.0, None], dtype=object),
], ids=["equal_python_numbers", "signed_zeros", "object_signed_zeros"])
def test_string_indexer_labels_are_what_str_prints(values):
    """Values that compare equal but print differently are two labels,
    in the fit, the fused fit and a fitted model's transform alike."""
    f = Frame({"lab": values})
    ref_labels, ref_out = _reference_index(values, "frequencyDesc")
    model, out = StringIndexer(inputCol="lab", outputCol="i").fit_transform(f)
    assert model.labels == ref_labels
    np.testing.assert_array_equal(out["i"], ref_out)
    again = StringIndexer(inputCol="lab", outputCol="i").fit(f)
    assert again.labels == ref_labels
    np.testing.assert_array_equal(again.transform(f)["i"], ref_out)


def test_estimator_default_fit_transform_is_fit_then_transform():
    """An estimator without its own ``fit_transform`` is fitted, then its
    model transforms the same frame; the last estimator is only fitted;
    the spans are ``stage.fit`` and ``stage.transform`` as before."""
    from sntc_tpu.obs import disable_tracing, enable_tracing

    f = Frame({"x": np.arange(4.0)})
    log = []
    first, last = _Recorder(log), _Recorder(log)
    model, out = first.fit_transform(f)
    assert [(w, o) for w, o, _ in log] == [("fit", first), ("transform", first)]
    assert log[0][2] is f and log[1][2] is f
    assert isinstance(model, _RecorderModel) and "seen" in out.columns

    log.clear()
    t = enable_tracing(capacity=256)
    t.clear()
    try:
        Pipeline(stages=[first, last]).fit(f)
        spans = t.spans()
    finally:
        disable_tracing()
    assert [(w, o) for w, o, _ in log] == [
        ("fit", first), ("transform", first), ("fit", last)]
    assert log[0][2] is f and log[1][2] is f
    assert "seen" in log[2][2].columns
    assert [(s["name"], s["attrs"]["stage"], s["attrs"]["index"])
            for s in sorted(spans, key=lambda s: s["t0"])
            if s["name"].startswith("stage.")] == [
        ("stage.fit", "_Recorder", 0),
        ("stage.transform", "_RecorderModel", 0),
        ("stage.fit", "_Recorder", 1),
    ]


def test_string_indexer_pipeline_fit_is_one_span():
    from sntc_tpu.obs import disable_tracing, enable_tracing, module_of

    f = _label_frame()
    t = enable_tracing(capacity=256)
    t.clear()
    try:
        Pipeline(stages=[StringIndexer(inputCol="label", outputCol="i"),
                         _Recorder()]).fit(f)
        spans = t.spans()
    finally:
        disable_tracing()
    assert [(s["name"], s["attrs"]["stage"], s["attrs"]["index"],
             s["attrs"]["module"])
            for s in sorted(spans, key=lambda s: s["t0"])
            if s["name"].startswith("stage.")] == [
        ("stage.fit_transform", "StringIndexer", 0, "feature"),
        ("stage.fit", "_Recorder", 1, module_of(_Recorder)),
    ]


# ---------------- VectorAssembler ----------------

def test_vector_assembler_stacks_in_order():
    f = Frame({
        "a": np.array([1.0, 2.0]),
        "b": np.array([[10.0, 20.0], [30.0, 40.0]]),
        "c": np.array([5.0, 6.0]),
    })
    out = VectorAssembler(inputCols=["a", "b", "c"]).transform(f)
    assert out["features"].dtype == np.float32
    np.testing.assert_array_equal(
        out["features"], [[1, 10, 20, 5], [2, 30, 40, 6]]
    )


def test_vector_assembler_n_by_1_columns_use_assign_path():
    """(N, 1) 2-D columns must NOT hit the all-1-D np.array fast path
    (np.array would stack them into 3-D)."""
    f = Frame({
        "a": np.array([[1.0], [2.0], [3.0]]),
        "b": np.array([[4.0], [5.0], [6.0]]),
    })
    out = VectorAssembler(inputCols=["a", "b"]).transform(f)
    assert out["features"].shape == (3, 2)
    np.testing.assert_array_equal(out["features"], [[1, 4], [2, 5], [3, 6]])


def _pooled(site):
    from sntc_tpu.obs import registry

    return registry().get("sntc_feature_pooled_copies_total", site=site) or 0


def _copy_bytes(**labels):
    from sntc_tpu.obs import registry

    return registry().get("sntc_feature_copy_bytes_total", **labels) or 0


@pytest.fixture
def eight_cores(monkeypatch):
    """What ``pool_workers`` reads of the machine, held still."""
    from sntc_tpu.feature import stack

    monkeypatch.setattr(stack.os, "sched_getaffinity",
                        lambda _pid: set(range(8)))


def _frame_with_bad_rows(scale):
    """``(frame, columns, rows with a NaN/Inf)``: the three-row frame, or
    eight columns whose stack is just over the pool's threshold."""
    from sntc_tpu.feature.stack import POOL_MIN_BYTES

    if scale == "three_rows":
        return Frame({"a": np.array([1.0, np.nan, 3.0])}), ["a"], [1]
    n = POOL_MIN_BYTES // (8 * 4) + 1
    cols = {f"c{j}": np.full(n, j, np.float32) for j in range(8)}
    cols["c0"][0] = np.nan
    cols["c3"][n // 2] = np.inf
    cols["c7"][n - 1] = -np.inf
    cols["c7"][0] = np.nan  # a row bad in two columns counts once
    return Frame(cols), list(cols), [0, n // 2, n - 1]


@pytest.mark.parametrize("scale", ["three_rows", "fit_scale"])
def test_vector_assembler_handle_invalid(scale, eight_cores):
    f, names, bad = _frame_with_bad_rows(scale)
    fit_scale = scale == "fit_scale"
    pooled0 = _pooled("assemble.stack")
    bytes0 = _copy_bytes(site="assemble.stack")
    with pytest.raises(ValueError, match=f"{len(bad)} rows contain NaN/Inf"):
        VectorAssembler(inputCols=names).transform(f)
    out = VectorAssembler(inputCols=names, handleInvalid="skip").transform(f)
    assert out.num_rows == f.num_rows - len(bad)
    assert np.isfinite(out["features"]).all()
    keep = VectorAssembler(inputCols=names, handleInvalid="keep")
    out = keep.transform(f)
    X = out["features"]
    assert out.num_rows == f.num_rows and X.dtype == np.float32
    assert np.flatnonzero(~np.isfinite(X).all(axis=1)).tolist() == bad
    assert np.isnan(X[bad[0], 0])
    # the (N, F) transpose of a C-contiguous [F, N] base, pooled or not
    assert X.flags.f_contiguous and X.base.flags.c_contiguous
    # three stacks: each counted whole, each through the pool at fit scale
    assert _pooled("assemble.stack") - pooled0 == (3 if fit_scale else 0)
    assert (_copy_bytes(site="assemble.stack") - bytes0
            == 3 * f.num_rows * len(names) * 4)
    # the same columns again: the memo's matrix, no fourth stack
    again = keep.transform(f.with_column("extra", np.zeros(f.num_rows)))
    assert (again["features"] is X) == fit_scale
    assert _pooled("assemble.stack") - pooled0 == (3 if fit_scale else 0)


def test_a_serving_batch_goes_through_no_pool(eight_cores):
    """A 4,096-row micro-batch stacks and takes by the single calls."""
    from sntc_tpu.data.schema import CICIDS2017_FEATURES
    from sntc_tpu.feature.selection import take_columns
    from sntc_tpu.obs import disable_tracing, enable_tracing

    rng = np.random.default_rng(8)
    names = list(CICIDS2017_FEATURES)
    f = Frame({c: rng.random(4096, dtype=np.float32) for c in names})
    before = _pooled("assemble.stack"), _pooled("select.take")
    t = enable_tracing(capacity=16)
    try:
        X = VectorAssembler(inputCols=names).transform(f)["features"]
        taken = take_columns(X, list(range(0, 78, 2)))
        spans = {s["name"]: s["attrs"] for s in t.spans()}
    finally:
        disable_tracing()
    assert (_pooled("assemble.stack"), _pooled("select.take")) == before
    assert spans["assemble.stack"] == {
        "columns": 78, "workers": 1, "module": "feature"}
    assert "assemble.finite_check" not in spans  # every column was clean
    assert spans["select.take"]["layout"] == "base_rows"
    np.testing.assert_array_equal(X, np.array([f[c] for c in names]).T)
    np.testing.assert_array_equal(taken, X[:, ::2])


# ---------------- stack_rows: the feature stages' pooled copy ----------------

_STACK_MIN_BYTES = 4096  # the threshold the helper is handed in this test


@pytest.mark.parametrize("planted", [None, np.nan, np.inf, -np.inf],
                         ids=["clean", "nan", "pos_inf", "neg_inf"])
@pytest.mark.parametrize("workers", [None, 1, 3],
                         ids=["planned", "one_worker", "three_workers"])
@pytest.mark.parametrize("size", ["under", "over"])
@pytest.mark.parametrize(
    "src", [np.float32, np.float64, np.int32, np.int64, np.bool_],
    ids=["float32", "float64", "int32", "int64", "bool"],
)
def test_stack_rows_is_np_array_with_a_finite_flag_a_row(
    src, size, workers, planted, eight_cores
):
    from sntc_tpu.feature.stack import pool_workers, stack_rows

    n_rows = 7
    width = _STACK_MIN_BYTES // (n_rows * 4) + (1 if size == "over" else 0)
    nbytes = n_rows * width * 4
    assert (nbytes >= _STACK_MIN_BYTES) == (size == "over")
    rng = np.random.default_rng(9)
    rows = [(rng.integers(-1000, 1000, width) * 1.5).astype(src)
            for _ in range(n_rows)]
    bad = []
    if planted is not None:
        # the first, a middle and the last row, at its first, a middle
        # and its last value
        for j, at in ((0, 0), (n_rows // 2, width // 2),
                      (n_rows - 1, width - 1)):
            rows[j] = rows[j].astype(np.float64)
            rows[j][at] = planted
            bad.append(j)
    planned = pool_workers(n_rows, nbytes, min_bytes=_STACK_MIN_BYTES)
    assert planned == (7 if size == "over" else 1)  # min(cores, rows, cap)
    want = np.array(rows, dtype=np.float32)
    got, finite = stack_rows(rows, np.float32, finite=True,
                             workers=planned if workers is None else workers)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == want.dtype and got.strides == want.strides
    assert got.flags.c_contiguous and got.flags.owndata
    assert got.flags.writeable and got.flags.aligned
    assert finite.dtype == bool
    assert np.flatnonzero(~finite).tolist() == bad
    unasked, none = stack_rows(rows, np.float32, workers=3)
    np.testing.assert_array_equal(unasked, want)
    assert none is None


def test_stack_rows_with_more_workers_than_cores_loses_no_row():
    """Every row is written by exactly one worker, whatever the
    interleaving: 32 workers, 203 rows, a thread switch every 1 us."""
    import sys

    from sntc_tpu.feature.stack import stack_rows

    rng = np.random.default_rng(10)
    rows = [rng.normal(size=257) for _ in range(203)]
    bad = [0, 17, 101, 202]
    for j in bad:
        rows[j][j] = np.inf
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(20):
            got, finite = stack_rows(rows, np.float32, finite=True,
                                     workers=32)
            np.testing.assert_array_equal(got, np.array(rows, np.float32))
            assert np.flatnonzero(~finite).tolist() == bad
    finally:
        sys.setswitchinterval(interval)


@pytest.mark.parametrize("workers", [1, 3])
def test_stack_rows_raises_a_worker_s_error(workers):
    from sntc_tpu.feature.stack import stack_rows

    rows = [np.zeros(8), np.zeros(8), np.zeros(5), np.zeros(8)]
    with pytest.raises(ValueError):
        stack_rows(rows, np.float32, workers=workers)


# ---------------- StandardScaler ----------------

def test_standard_scaler_matches_numpy_unbiased(mesh8):
    rng = np.random.default_rng(0)
    X = rng.normal(3.0, 2.0, size=(500, 6)).astype(np.float32)
    X[:, 5] = 7.0  # constant feature -> std 0 -> output 0 (Spark semantics)
    f = Frame({"features": X})
    model = StandardScaler(
        mesh=mesh8, inputCol="features", outputCol="scaled", withMean=True
    ).fit(f)
    np.testing.assert_allclose(model.mean, X.mean(0), rtol=1e-4)
    np.testing.assert_allclose(model.std[:5], X.std(0, ddof=1)[:5], rtol=1e-3)
    out = model.transform(f)["scaled"]
    np.testing.assert_allclose(out[:, :5].mean(0), 0.0, atol=1e-4)
    np.testing.assert_allclose(out[:, :5].std(0, ddof=1), 1.0, rtol=1e-3)
    assert np.all(out[:, 5] == 0.0)


def test_standard_scaler_no_mean_default():
    X = np.array([[2.0], [4.0]], dtype=np.float32)
    model = StandardScaler(inputCol="features", outputCol="s").fit(
        Frame({"features": X})
    )
    out = model.transform(Frame({"features": X}))["s"]
    # withMean=False: scaled but not centered
    np.testing.assert_allclose(out.ravel(), X.ravel() / X.std(ddof=1), rtol=1e-5)


# ---------------- ChiSqSelector ----------------

def test_chi_square_matches_scipy():
    from scipy.stats import chi2_contingency

    from sntc_tpu.ops.histogram import chi_square

    rng = np.random.default_rng(1)
    observed = rng.integers(1, 50, size=(3, 4, 5)).astype(np.float64)
    stats, pvals, dofs = chi_square(observed)
    for j in range(3):
        ref = chi2_contingency(observed[j], correction=False)
        assert stats[j] == pytest.approx(ref.statistic, rel=1e-9)
        assert pvals[j] == pytest.approx(ref.pvalue, rel=1e-9)
        assert dofs[j] == ref.dof


def test_chisq_selector_picks_informative_features(mesh8):
    rng = np.random.default_rng(2)
    n = 2000
    y = rng.integers(0, 3, size=n)
    X = rng.normal(size=(n, 10)).astype(np.float32)
    # features 2 and 7 carry the label signal
    X[:, 2] += y * 2.0
    X[:, 7] -= y * 1.5
    f = Frame({"features": X, "label": y.astype(np.float64)})
    model = ChiSqSelector(
        mesh=mesh8, numTopFeatures=2, labelCol="label"
    ).fit(f)
    assert model.selected_features == [2, 7]
    out = model.transform(f)
    assert out["selectedFeatures"].shape == (n, 2)
    np.testing.assert_array_equal(out["selectedFeatures"][:, 0], X[:, 2])


def test_chisq_selector_fpr_mode(mesh8):
    rng = np.random.default_rng(3)
    n = 3000
    y = rng.integers(0, 2, size=n)
    X = rng.normal(size=(n, 6)).astype(np.float32)
    X[:, 0] += y * 3.0
    model = ChiSqSelector(
        mesh=mesh8, selectorType="fpr", fpr=1e-6, labelCol="label"
    ).fit(Frame({"features": X, "label": y.astype(np.float64)}))
    assert model.selected_features == [0]



# ---------------- take_columns: the package's one column-take ----------------

def _matrix_in(layout):
    """An ``(N, 6)`` float32 matrix laid out as named, and the branch
    ``take_columns`` has to choose for it from what it can observe."""
    if layout == "feature_major_fit_scale":
        # three of its columns are just over the pool's threshold
        from sntc_tpu.feature.stack import POOL_MIN_BYTES

        n = POOL_MIN_BYTES // (3 * 4) + 1
        return (np.arange(6 * n, dtype=np.float32).reshape(6, n).T,
                "base_rows")
    base = np.arange(6 * 50, dtype=np.float32).reshape(6, 50)  # [F, N]
    row_major = np.ascontiguousarray(base.T)
    if layout == "jax_array":
        import jax.numpy as jnp

        return jnp.asarray(row_major), "generic"
    return {
        # the assembler's fast path: a feature-major base, transposed
        "feature_major": (base.T, "base_rows"),
        "row_major": (row_major, "columns"),
        "strided_rows": (row_major[::2], "generic"),
        "feature_major_strided_rows": (base.T[::2], "generic"),
        # contiguous both ways: either copy is the same memcpy
        "n_by_1": (base.T[:, :1], "columns"),
        "zero_rows": (base.T[:0], "columns"),
    }[layout]


@pytest.mark.parametrize(
    "selection", [[0, 2, 5], [5, 0, 2], [3, 3, 0], []],
    ids=["sorted", "unsorted", "repeated", "empty"],
)
@pytest.mark.parametrize("layout", [
    "feature_major", "row_major", "strided_rows",
    "feature_major_strided_rows", "n_by_1", "zero_rows", "jax_array",
    "feature_major_fit_scale",
])
def test_take_columns_equals_the_fancy_index_in_every_layout(
    layout, selection, eight_cores
):
    from sntc_tpu.feature.selection import take_columns
    from sntc_tpu.obs import disable_tracing, enable_tracing

    X, branch = _matrix_in(layout)
    idx = [0] * len(selection) if X.shape[1] == 1 else selection
    want = np.ascontiguousarray(np.asarray(X)[:, np.asarray(idx, np.intp)])
    before = _copy_bytes(site="select.take", layout=branch)
    pooled = _pooled("select.take")
    t = enable_tracing(capacity=16)
    try:
        got = take_columns(X, idx)
        (taken,) = [s for s in t.spans() if s["name"] == "select.take"]
    finally:
        disable_tracing()
    assert isinstance(got, np.ndarray)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    assert taken["attrs"] == {"layout": branch, "module": "feature"}
    assert (_copy_bytes(site="select.take", layout=branch) - before
            == want.nbytes)
    # a pool made the copy only where it is fit-scale
    assert _pooled("select.take") - pooled == int(
        layout == "feature_major_fit_scale" and len(idx) > 1)
    if branch == "base_rows" and len(idx) > 1:
        # the result stays feature-major: whole rows of a new base
        assert got.flags.f_contiguous and not got.flags.c_contiguous
    assert not np.shares_memory(got, np.asarray(X))


def test_selector_models_and_slicer_share_the_one_take():
    from sntc_tpu.feature import (
        ChiSqSelectorModel,
        UnivariateFeatureSelectorModel,
        VectorSlicer,
    )

    X, _ = _matrix_in("feature_major")
    f = Frame({"features": X})
    before = _copy_bytes(site="select.take", layout="base_rows")
    outs = [
        ChiSqSelectorModel(selected_features=[1, 4]).transform(f)[
            "selectedFeatures"],
        UnivariateFeatureSelectorModel(selected_features=[1, 4]).transform(f)[
            "selectedFeatures"],
        VectorSlicer(indices=[1, 4]).transform(f)["sliced"],
    ]
    for out in outs:
        np.testing.assert_array_equal(out, X[:, [1, 4]])
        assert out.flags.f_contiguous
    assert (_copy_bytes(site="select.take", layout="base_rows") - before
            == 3 * X.shape[0] * 2 * 4)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_chisq_selector_fit_copies_only_to_cast(mesh8, monkeypatch, dtype):
    """A float32 column reaches ``chi2_scores`` (and so ``shard_batch``) as
    the frame's own array; a float64 one as a float32 cast of it."""
    from sntc_tpu.feature import chisq_selector

    rng = np.random.default_rng(5)
    y = rng.integers(0, 3, size=500)
    col = (rng.normal(size=(4, 500)) + y).astype(dtype).T  # feature-major
    seen = []
    scores = chisq_selector.chi2_scores
    monkeypatch.setattr(
        chisq_selector, "chi2_scores",
        lambda X, *a, **k: seen.append(X) or scores(X, *a, **k),
    )
    before = _copy_bytes(site="chi2.extract")
    ChiSqSelector(mesh=mesh8, numTopFeatures=2, labelCol="label").fit(
        Frame({"features": col, "label": y.astype(np.float64)})
    )
    (X,) = seen
    assert X.dtype == np.float32
    np.testing.assert_array_equal(X, col.astype(np.float32))
    if dtype is np.float32:
        assert X is col
        assert _copy_bytes(site="chi2.extract") == before
    else:
        assert not np.shares_memory(X, col)
        assert _copy_bytes(site="chi2.extract") - before == 500 * 4 * 4


def test_chisq_selector_refit_on_one_frame_finds_its_matrix_on_the_device(
    mesh8,
):
    """What ``copy=False`` decides for the device cache: the upload is
    keyed on the frame's own column, so a second fit on the same frame
    re-uploads the labels and the row weights, not the matrix."""
    from sntc_tpu.obs import registry

    def uploaded():
        return registry().get("sntc_transfer_upload_bytes_total") or 0

    rng = np.random.default_rng(6)
    n = 40_000  # 8 columns of float32: over the cache's 1 MiB floor
    y = rng.integers(0, 3, size=n)
    col = (rng.normal(size=(8, n)) + y).astype(np.float32).T
    f = Frame({"features": col, "label": y.astype(np.float64)})
    est = ChiSqSelector(mesh=mesh8, numTopFeatures=2, labelCol="label")
    b0 = uploaded()
    first = est.fit(f)
    b1 = uploaded()
    again = est.fit(f)
    b2 = uploaded()
    assert again.selected_features == first.selected_features
    # the first fit's uploads less the second's are the (padded) matrix
    assert 0 < b2 - b1 < col.nbytes
    assert (b1 - b0) - (b2 - b1) >= col.nbytes


# ---- the benchmark's four-stage pipeline, from either assembler layout ----

_PIPELINE_ROWS = 4_000


def _four_stage_fit(mesh, assembler_cls):
    """One fit of ``benchmark/estimators/rf.py``'s pipeline on a clean
    frame: the fitted product, the bytes it added to
    ``sntc_feature_copy_bytes_total`` and the layouts its takes chose."""
    from sntc_tpu.core.base import Pipeline
    from sntc_tpu.data.schema import CICIDS2017_FEATURES
    from sntc_tpu.data.synth import generate_frame
    from sntc_tpu.models import RandomForestClassifier
    from sntc_tpu.obs import disable_tracing, enable_tracing, registry

    def counted():
        snap = registry().snapshot().get(
            "sntc_feature_copy_bytes_total", {"series": []}
        )
        return {
            tuple(sorted(s["labels"].items())): s["value"]
            for s in snap["series"]
        }

    frame = generate_frame(_PIPELINE_ROWS, seed=31, dirty=False)
    pipe = Pipeline(stages=[
        StringIndexer(inputCol="Label", outputCol="label",
                      handleInvalid="skip"),
        assembler_cls(inputCols=list(CICIDS2017_FEATURES),
                      outputCol="rawFeatures", handleInvalid="skip"),
        ChiSqSelector(mesh=mesh, numTopFeatures=40,
                      featuresCol="rawFeatures", labelCol="label",
                      outputCol="features"),
        RandomForestClassifier(mesh=mesh, numTrees=20, maxDepth=5,
                               maxBins=32, seed=11, featuresCol="features"),
    ])
    before = counted()
    t = enable_tracing(capacity=256)
    try:
        model = pipe.fit(frame)
        layouts = [s["attrs"]["layout"] for s in t.spans()
                   if s["name"] == "select.take"]
    finally:
        disable_tracing()
    after = counted()
    stages = model.getStages()
    forest = stages[-1].forest
    return {
        "selected": np.asarray(stages[2].selected_features),
        "feature": np.asarray(forest.feature),
        "threshold": np.asarray(forest.threshold),
        "leaf_stats": np.asarray(forest.leaf_stats),
        "added": {k: v - before.get(k, 0) for k, v in after.items()
                  if v != before.get(k, 0)},
        "layouts": layouts,
    }


class _RowMajorAssembler(VectorAssembler):
    """The assembler with its output forced into a C-order matrix."""

    def transform(self, frame):
        out = super().transform(frame)
        name = self.getOutputCol()
        return out.with_column(name, np.ascontiguousarray(out[name]))


@pytest.fixture(scope="module")
def four_stage_fits(mesh8):
    return {
        "feature_major": _four_stage_fit(mesh8, VectorAssembler),
        "row_major": _four_stage_fit(mesh8, _RowMajorAssembler),
    }


def test_forest_is_the_same_from_either_assembler_layout(four_stage_fits):
    got, want = four_stage_fits["feature_major"], four_stage_fits["row_major"]
    assert len(got["selected"]) == 40
    assert (got["feature"] >= 0).sum() > 20  # real trees, not stumps
    for name in ("selected", "feature", "threshold", "leaf_stats"):
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


@pytest.mark.parametrize("layout, branch", [
    ("feature_major", "base_rows"), ("row_major", "columns"),
])
def test_one_fit_counts_the_copies_it_made(four_stage_fits, layout, branch):
    """The stack and the take, each once; nothing to obtain float32."""
    fit = four_stage_fits[layout]
    assert fit["layouts"] == [branch]
    assert fit["added"] == {
        (("site", "assemble.stack"),): _PIPELINE_ROWS * 78 * 4,
        (("layout", branch), ("site", "select.take")):
            _PIPELINE_ROWS * 40 * 4,
    }


def test_observability_doc_lists_the_take():
    import os

    with open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "OBSERVABILITY.md",
    )) as f:
        doc = f.read()
    assert "`sntc_feature_copy_bytes_total` | counter | layout, site" in doc
    assert "| `select.take` | `feature/selection.py:take_columns`" in doc
    assert "`sntc_feature_pooled_copies_total` | counter | site" in doc


def test_chisq_selector_fdr_and_fwe_modes(mesh8):
    """fdr = Benjamini-Hochberg step-up on sorted p-values; fwe =
    Bonferroni p < fwe/F (Spark ChiSqSelector selectorType parity)."""
    rng = np.random.default_rng(4)
    n = 3000
    y = rng.integers(0, 2, size=n)
    X = rng.normal(size=(n, 8)).astype(np.float32)
    X[:, 1] += y * 3.0
    X[:, 5] += y * 2.5
    f = Frame({"features": X, "label": y.astype(np.float64)})
    fdr_model = ChiSqSelector(
        mesh=mesh8, selectorType="fdr", fdr=1e-4, labelCol="label"
    ).fit(f)
    assert fdr_model.selected_features == [1, 5]
    fwe_model = ChiSqSelector(
        mesh=mesh8, selectorType="fwe", fwe=1e-4, labelCol="label"
    ).fit(f)
    assert fwe_model.selected_features == [1, 5]
    # BH with a loose budget keeps at least everything Bonferroni keeps
    loose = ChiSqSelector(
        mesh=mesh8, selectorType="fdr", fdr=0.5, labelCol="label"
    ).fit(f)
    assert set(loose.selected_features) >= {1, 5}


# ---------------- UnivariateFeatureSelector ----------------

def test_ufs_anova_matches_sklearn(mesh8):
    from sklearn.feature_selection import f_classif as sk_f_classif

    from sntc_tpu.feature import UnivariateFeatureSelector

    rng = np.random.default_rng(6)
    n = 4000
    y = rng.integers(0, 3, size=n)
    X = rng.normal(size=(n, 10)).astype(np.float32)
    X[:, 3] += y * 1.5
    X[:, 8] -= y * 2.0
    f = Frame({"features": X, "label": y.astype(np.float64)})
    sel = UnivariateFeatureSelector(
        mesh=mesh8, featureType="continuous", labelType="categorical",
        selectionMode="numTopFeatures", selectionThreshold=2,
    ).fit(f)
    assert sorted(sel.selected_features) == [3, 8]
    # statistic parity with sklearn's f_classif
    from sntc_tpu.feature.univariate_selector import (
        _anova_moments_agg,
        f_classif,
    )
    from sntc_tpu.parallel.collectives import shard_batch

    import jax.numpy as jnp

    xs, ys, w = shard_batch(mesh8, X, y.astype(np.int32))
    F, p = f_classif(_anova_moments_agg(mesh8, 3)(xs, ys, w, jnp.asarray(X[0])))
    F_sk, p_sk = sk_f_classif(X.astype(np.float64), y)
    np.testing.assert_allclose(F, F_sk, rtol=2e-3)
    out = sel.transform(f)
    assert out["selectedFeatures"].shape == (n, 2)


def test_ufs_f_regression_matches_sklearn(mesh8):
    from sklearn.feature_selection import f_regression as sk_f_regression

    from sntc_tpu.feature import UnivariateFeatureSelector

    rng = np.random.default_rng(7)
    n = 3000
    X = rng.normal(size=(n, 8)).astype(np.float32)
    y = 2.0 * X[:, 1] - 1.0 * X[:, 6] + 0.5 * rng.normal(size=n)
    f = Frame({"features": X, "label": y})
    sel = UnivariateFeatureSelector(
        mesh=mesh8, featureType="continuous", labelType="continuous",
        selectionMode="numTopFeatures", selectionThreshold=2,
    ).fit(f)
    assert sorted(sel.selected_features) == [1, 6]
    from sntc_tpu.feature.univariate_selector import (
        _regression_moments_agg,
        f_regression,
    )
    from sntc_tpu.parallel.collectives import shard_batch

    import jax.numpy as jnp

    xs, ys, w = shard_batch(mesh8, X, y.astype(np.float32))
    F, p = f_regression(
        _regression_moments_agg(mesh8)(
            xs, ys, w, jnp.asarray(X[0]), jnp.float32(y[0])
        )
    )
    F_sk, p_sk = sk_f_regression(X.astype(np.float64), y)
    np.testing.assert_allclose(F, F_sk, rtol=5e-3)


def test_ufs_chi2_mode_and_validation(mesh8):
    from sntc_tpu.feature import ChiSqSelector, UnivariateFeatureSelector

    rng = np.random.default_rng(8)
    n = 2500
    y = rng.integers(0, 2, size=n)
    X = rng.normal(size=(n, 6)).astype(np.float32)
    X[:, 2] += y * 3.0
    f = Frame({"features": X, "label": y.astype(np.float64)})
    # categorical/categorical == ChiSqSelector's χ² (binned continuous)
    ufs = UnivariateFeatureSelector(
        mesh=mesh8, featureType="categorical", labelType="categorical",
        selectionMode="numTopFeatures", selectionThreshold=1,
    ).fit(f)
    chi = ChiSqSelector(mesh=mesh8, numTopFeatures=1).fit(f)
    assert ufs.selected_features == chi.selected_features == [2]
    with pytest.raises(ValueError, match="featureType and labelType"):
        UnivariateFeatureSelector(mesh=mesh8).fit(f)
    with pytest.raises(ValueError, match="no\\s+Spark score function"):
        UnivariateFeatureSelector(
            mesh=mesh8, featureType="categorical", labelType="continuous"
        ).fit(f)


def test_ufs_save_load(tmp_path, mesh8):
    from sntc_tpu.feature import UnivariateFeatureSelector
    from sntc_tpu.mlio import load_model, save_model

    rng = np.random.default_rng(9)
    X = rng.normal(size=(1000, 5)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    X[:, 0] += 1.0
    f = Frame({"features": X, "label": y})
    m = UnivariateFeatureSelector(
        mesh=mesh8, featureType="continuous", labelType="categorical",
        selectionMode="fpr", selectionThreshold=1e-8,
    ).fit(f)
    save_model(m, str(tmp_path / "ufs"))
    m2 = load_model(str(tmp_path / "ufs"))
    assert m2.selected_features == m.selected_features == [0]


def test_ufs_threshold_validation(mesh8):
    from sntc_tpu.feature import UnivariateFeatureSelector

    rng = np.random.default_rng(10)
    f = Frame({
        "features": rng.normal(size=(200, 4)).astype(np.float32),
        "label": rng.integers(0, 2, 200).astype(np.float64),
    })
    with pytest.raises(ValueError, match="positive\\s+feature count"):
        UnivariateFeatureSelector(
            mesh=mesh8, featureType="continuous", labelType="categorical",
            selectionMode="numTopFeatures", selectionThreshold=-3,
        ).fit(f)
    with pytest.raises(ValueError, match="in \\[0, 1\\]"):
        UnivariateFeatureSelector(
            mesh=mesh8, featureType="continuous", labelType="categorical",
            selectionMode="fpr", selectionThreshold=3.0,
        ).fit(f)


# ---------------- MinMax/MaxAbs/Normalizer/Binarizer/PCA ----------------

def test_minmax_scaler_matches_sklearn(mesh8):
    from sklearn.preprocessing import MinMaxScaler as SkMM

    from sntc_tpu.feature import MinMaxScaler

    rng = np.random.default_rng(12)
    X = rng.normal(size=(1000, 5)).astype(np.float32) * 3
    X[:, 4] = 2.0  # constant feature
    f = Frame({"features": X})
    m = MinMaxScaler(mesh=mesh8).fit(f)
    out = np.asarray(m.transform(f)["scaledFeatures"])
    sk = SkMM().fit_transform(X[:, :4])
    np.testing.assert_allclose(out[:, :4], sk, atol=1e-5)
    assert np.all(out[:, 4] == 0.5)  # Spark: constant -> midpoint
    m2 = MinMaxScaler(mesh=mesh8, min=-1.0, max=3.0).fit(f)
    out2 = np.asarray(m2.transform(f)["scaledFeatures"])
    np.testing.assert_allclose(out2[:, :4], sk * 4.0 - 1.0, atol=2e-4)
    assert np.all(out2[:, 4] == 1.0)
    with pytest.raises(ValueError, match="min must be"):
        MinMaxScaler(mesh=mesh8, min=2.0, max=1.0).fit(f)


def test_robust_scaler_matches_sklearn(mesh8):
    from sklearn.preprocessing import RobustScaler as SkRS

    from sntc_tpu.feature import RobustScaler

    rng = np.random.default_rng(21)
    X = rng.lognormal(1.0, 2.0, size=(1001, 4)).astype(np.float32)
    X[:, 3] = 7.0  # zero-IQR feature
    f = Frame({"features": X})
    # default: scale only, no centering (Spark's defaults)
    m = RobustScaler().fit(f)
    out = np.asarray(m.transform(f)["scaledFeatures"])
    sk = SkRS(with_centering=False).fit_transform(X[:, :3])
    np.testing.assert_allclose(out[:, :3], sk, rtol=2e-4)
    assert np.all(out[:, 3] == 0.0)  # zero range -> 0 (Spark std=0 rule)
    # centered + custom quantile range
    m2 = RobustScaler(
        withCentering=True, lower=0.1, upper=0.9
    ).fit(f)
    out2 = np.asarray(m2.transform(f)["scaledFeatures"])
    sk2 = SkRS(quantile_range=(10, 90)).fit_transform(
        X[:, :3].astype(np.float64)
    )
    np.testing.assert_allclose(out2[:, :3], sk2, atol=2e-3)
    with pytest.raises(ValueError, match="lower must be"):
        RobustScaler(lower=0.8, upper=0.2).fit(f)


def test_robust_scaler_save_load(mesh8, tmp_path):
    from sntc_tpu.feature import RobustScaler
    from sntc_tpu.mlio.save_load import load_model, save_model

    X = np.random.default_rng(3).normal(size=(256, 3)).astype(np.float32)
    f = Frame({"features": X})
    m = RobustScaler(withCentering=True).fit(f)
    save_model(m, str(tmp_path / "rs"))
    m2 = load_model(str(tmp_path / "rs"))
    np.testing.assert_allclose(m2.median, m.median)
    np.testing.assert_allclose(m2.range, m.range)
    np.testing.assert_allclose(
        m2.transform(f)["scaledFeatures"], m.transform(f)["scaledFeatures"]
    )


def test_maxabs_scaler(mesh8):
    from sntc_tpu.feature import MaxAbsScaler

    X = np.array([[2.0, -4.0, 0.0], [-1.0, 8.0, 0.0]], np.float32)
    m = MaxAbsScaler(mesh=mesh8).fit(Frame({"features": X}))
    np.testing.assert_allclose(m.maxAbs, [2.0, 8.0, 0.0])
    out = m.transform(Frame({"features": X}))["scaledFeatures"]
    np.testing.assert_allclose(
        out, [[1.0, -0.5, 0.0], [-0.5, 1.0, 0.0]], atol=1e-6
    )


def test_normalizer_and_binarizer():
    from sntc_tpu.feature import Binarizer, Normalizer

    X = np.array([[3.0, 4.0], [0.0, 0.0], [1.0, 1.0]], np.float32)
    f = Frame({"features": X})
    out = Normalizer(inputCol="features", outputCol="n").transform(f)["n"]
    np.testing.assert_allclose(out[0], [0.6, 0.8], atol=1e-6)
    np.testing.assert_allclose(out[1], [0.0, 0.0])  # zero row unchanged
    out1 = Normalizer(inputCol="features", outputCol="n", p=1.0).transform(f)["n"]
    np.testing.assert_allclose(out1[2], [0.5, 0.5], atol=1e-6)
    outi = Normalizer(
        inputCol="features", outputCol="n", p=float("inf")
    ).transform(f)["n"]
    np.testing.assert_allclose(outi[0], [0.75, 1.0], atol=1e-6)
    b = Binarizer(inputCol="features", outputCol="b", threshold=0.5).transform(f)
    np.testing.assert_array_equal(
        b["b"], [[1.0, 1.0], [0.0, 0.0], [1.0, 1.0]]
    )


def test_pca_matches_sklearn(mesh8):
    from sklearn.decomposition import PCA as SkPCA

    from sntc_tpu.feature import PCA
    from sntc_tpu.mlio import load_model, save_model

    rng = np.random.default_rng(13)
    base = rng.normal(size=(2000, 2)).astype(np.float32)
    mix = np.array([[1.0, 0.5, 0.1, 0.0], [0.0, 0.3, 1.0, 0.2]], np.float32)
    X = base @ mix + 0.01 * rng.normal(size=(2000, 4)).astype(np.float32)
    f = Frame({"features": X})
    m = PCA(mesh=mesh8, k=2).fit(f)
    sk = SkPCA(n_components=2).fit(X.astype(np.float64))
    # components match up to sign
    for j in range(2):
        dot = abs(np.dot(m.pc[:, j], sk.components_[j]))
        assert dot == pytest.approx(1.0, abs=1e-3)
    np.testing.assert_allclose(
        m.explainedVariance, sk.explained_variance_ratio_, atol=1e-4
    )
    # Spark projects raw (uncentered) vectors
    out = np.asarray(m.transform(f)["pcaFeatures"])
    np.testing.assert_allclose(out, X @ m.pc, atol=1e-4)
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        save_model(m, d + "/pca")
        m2 = load_model(d + "/pca")
        np.testing.assert_allclose(m2.pc, m.pc)
    with pytest.raises(ValueError, match="exceeds the feature width"):
        PCA(mesh=mesh8, k=9).fit(f)


def test_pca_large_mean_stability(mesh8):
    """Covariance accumulates about a pilot row: large feature means must
    not destroy the components (f32 cancellation hazard)."""
    from sklearn.decomposition import PCA as SkPCA

    from sntc_tpu.feature import PCA

    rng = np.random.default_rng(14)
    base = rng.normal(size=(20000, 2)).astype(np.float32)
    mix = np.array([[1.0, 0.5, 0.1], [0.0, 0.3, 1.0]], np.float32)
    X = (base @ mix + np.array([1e3, 5e3, 2e3], np.float32)).astype(np.float32)
    m = PCA(mesh=mesh8, k=2).fit(Frame({"features": X}))
    sk = SkPCA(n_components=2).fit(X.astype(np.float64))
    for j in range(2):
        assert abs(np.dot(m.pc[:, j], sk.components_[j])) > 0.999
    np.testing.assert_allclose(
        m.explainedVariance, sk.explained_variance_ratio_, atol=2e-3
    )


def test_ufs_rejects_fractional_top_k(mesh8):
    from sntc_tpu.feature import UnivariateFeatureSelector

    f = Frame({
        "features": np.zeros((10, 3), np.float32),
        "label": np.zeros(10),
    })
    with pytest.raises(ValueError, match="integer\\s+feature count"):
        UnivariateFeatureSelector(
            mesh=mesh8, featureType="continuous", labelType="categorical",
            selectionMode="numTopFeatures", selectionThreshold=2.7,
        ).fit(f)


# ---------------- Bucketizer / QuantileDiscretizer / Imputer ----------------

def test_bucketizer_spark_semantics():
    from sntc_tpu.feature import Bucketizer

    f = Frame({"x": np.array([-1.0, 0.0, 0.5, 1.0, 2.0, 3.0])})
    b = Bucketizer(inputCol="x", outputCol="b", splits=[0.0, 1.0, 2.0, 3.0])
    # out-of-range ALWAYS errors regardless of handleInvalid (Spark)
    with pytest.raises(ValueError, match="outside the splits"):
        b.transform(f)
    with pytest.raises(ValueError, match="outside the splits"):
        b.copy({"handleInvalid": "keep"}).transform(f)
    fin = Frame({"x": np.array([0.0, 0.5, 1.0, 2.0, 3.0, np.nan])})
    with pytest.raises(ValueError, match="NaN"):
        b.transform(fin)
    kept = b.copy({"handleInvalid": "keep"}).transform(fin)
    # last bucket closed on the right: 3.0 -> bucket 2; NaN -> extra 3
    np.testing.assert_array_equal(kept["b"], [0.0, 0.0, 1.0, 2.0, 2.0, 3.0])
    skipped = b.copy({"handleInvalid": "skip"}).transform(fin)
    assert skipped.num_rows == 5
    with pytest.raises(ValueError, match="strictly increasing"):
        Bucketizer(inputCol="x", outputCol="b", splits=[0.0, 0.0, 1.0]).transform(f)


def test_quantile_discretizer_matches_quantiles():
    from sntc_tpu.feature import QuantileDiscretizer

    with pytest.raises(ValueError, match="no non-NaN values"):
        QuantileDiscretizer(inputCol="x", numBuckets=3).fit(
            Frame({"x": np.array([np.nan, np.nan])})
        )

    rng = np.random.default_rng(15)
    x = rng.normal(size=5000)
    f = Frame({"x": x})
    model = QuantileDiscretizer(
        inputCol="x", outputCol="q", numBuckets=4
    ).fit(f)
    out = model.transform(f)
    counts = np.bincount(np.asarray(out["q"], np.int64))
    # quartile buckets are balanced
    assert counts.size == 4 and counts.min() > 0.2 * len(x)
    # open ends: extreme values don't error
    far = model.transform(Frame({"x": np.array([-1e9, 1e9])}))
    np.testing.assert_array_equal(far["q"], [0.0, 3.0])


def test_imputer_mean_median_roundtrip(tmp_path):
    from sntc_tpu.feature import Imputer
    from sntc_tpu.mlio import load_model, save_model

    a = np.array([1.0, np.nan, 3.0, np.nan])
    b = np.array([10.0, 20.0, -1.0, 40.0])
    f = Frame({"a": a, "b": b})
    m = Imputer(inputCols=["a", "b"], outputCols=["a2", "b2"]).fit(f)
    out = m.transform(f)
    np.testing.assert_allclose(out["a2"], [1.0, 2.0, 3.0, 2.0])
    np.testing.assert_allclose(out["b2"], b)  # no NaN in b
    med = Imputer(
        inputCols=["b"], strategy="median", missingValue=-1.0
    ).fit(f)
    np.testing.assert_allclose(
        med.surrogates, [np.median([10.0, 20.0, 40.0])]
    )
    out2 = med.transform(f)
    assert out2["b"][2] == 20.0
    save_model(m, str(tmp_path / "imp"))
    m2 = load_model(str(tmp_path / "imp"))
    np.testing.assert_allclose(
        np.asarray(m2.transform(f)["a2"]), np.asarray(out["a2"])
    )


# ---------------- OneHotEncoder / VectorSlicer / ElementwiseProduct ---------

def test_one_hot_encoder_spark_semantics(tmp_path):
    from sntc_tpu.feature import OneHotEncoder
    from sntc_tpu.mlio import load_model, save_model

    f = Frame({"cat": np.array([0.0, 1.0, 2.0, 1.0])})
    m = OneHotEncoder(inputCols=["cat"]).fit(f)
    assert m.categorySizes == [3]
    out = m.transform(f)["cat_ohe"]
    # dropLast: category 2 encodes as all-zeros, width 2
    np.testing.assert_array_equal(
        out, [[1, 0], [0, 1], [0, 0], [0, 1]]
    )
    full = m.copy({"dropLast": False}).transform(f)["cat_ohe"]
    np.testing.assert_array_equal(
        full, [[1, 0, 0], [0, 1, 0], [0, 0, 1], [0, 1, 0]]
    )
    unseen = Frame({"cat": np.array([0.0, 5.0])})
    with pytest.raises(ValueError, match="outside"):
        m.transform(unseen)
    kept = m.copy({"handleInvalid": "keep", "dropLast": False}).transform(
        unseen
    )["cat_ohe"]
    # keep: extra invalid slot appended
    np.testing.assert_array_equal(kept, [[1, 0, 0, 0], [0, 0, 0, 1]])
    save_model(m, str(tmp_path / "ohe"))
    m2 = load_model(str(tmp_path / "ohe"))
    np.testing.assert_array_equal(
        np.asarray(m2.transform(f)["cat_ohe"]), np.asarray(out)
    )
    with pytest.raises(ValueError, match="non-negative"):
        OneHotEncoder(inputCols=["cat"]).fit(
            Frame({"cat": np.array([0.5, 1.0])})
        )


def test_vector_slicer_and_elementwise_product():
    from sntc_tpu.feature import ElementwiseProduct, VectorSlicer

    X = np.arange(12, dtype=np.float32).reshape(3, 4)
    f = Frame({"features": X})
    out = VectorSlicer(indices=[3, 0]).transform(f)["sliced"]
    np.testing.assert_array_equal(out, X[:, [3, 0]])
    with pytest.raises(ValueError, match="out of range"):
        VectorSlicer(indices=[9]).transform(f)
    ew = ElementwiseProduct(scalingVec=[1.0, 0.0, 2.0, -1.0]).transform(f)
    np.testing.assert_allclose(
        ew["scaled"], X * np.array([1.0, 0.0, 2.0, -1.0])
    )
    with pytest.raises(ValueError, match="length"):
        ElementwiseProduct(scalingVec=[1.0]).transform(f)


# ---------------- PolynomialExpansion / Interaction ----------------

def test_polynomial_expansion_spark_order():
    from sntc_tpu.feature import PolynomialExpansion
    from sntc_tpu.feature.expansion import _expansion_plan
    from math import comb

    # Spark's documented degree-2 order for [x1, x2]:
    # x1, x1², x2, x1x2, x2²
    f = Frame({"v": np.array([[2.0, 3.0], [1.0, -1.0]])})
    out = PolynomialExpansion(inputCol="v", outputCol="p").transform(f)["p"]
    np.testing.assert_allclose(
        out, [[2, 4, 3, 6, 9], [1, 1, -1, -1, 1]]
    )
    # width = C(n+d, d) - 1 for several shapes
    for n, d in ((3, 2), (4, 3), (5, 2)):
        assert len(_expansion_plan(n, d)) == comb(n + d, d) - 1
    # degree-3 prefix for one variable: x1, x1², x1³
    plan = _expansion_plan(2, 3)
    assert plan[:3] == ((0,), (0, 0), (0, 0, 0))


def test_interaction_layout_and_scalars():
    from sntc_tpu.feature import Interaction

    f = Frame({
        "a": np.array([2.0, 3.0]),
        "v": np.array([[1.0, 10.0], [2.0, 20.0]]),
        "w": np.array([[5.0, 7.0], [1.0, 1.0]]),
    })
    out = Interaction(inputCols=["a", "v", "w"], outputCol="i").transform(f)
    # width = 1*2*2; LAST input varies fastest
    np.testing.assert_allclose(
        out["i"],
        [[2 * 1 * 5, 2 * 1 * 7, 2 * 10 * 5, 2 * 10 * 7],
         [3 * 2 * 1, 3 * 2 * 1, 3 * 20 * 1, 3 * 20 * 1]],
    )
    with pytest.raises(ValueError, match="at least two"):
        Interaction(inputCols=["a"]).transform(f)
