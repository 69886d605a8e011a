"""Adapter of the ``rf`` estimator kind: ChiSqSelector + random forest.
How the program is built for the configuration, the work one pass needs from
shapes, and the comparison of what a timed fit produced with the plain
reference (``benchmark/reference.py``)."""

from __future__ import annotations

import numpy as np

import gen
import reference as ref


def build_pipeline(cfg, mesh, seed):
    from sntc_tpu.core.base import Pipeline
    from sntc_tpu.feature import ChiSqSelector, StringIndexer, VectorAssembler
    from sntc_tpu.models import RandomForestClassifier

    schema = gen.load_schema()
    return Pipeline(stages=[
        StringIndexer(inputCol=schema["label_column"], outputCol="label",
                      handleInvalid="skip"),
        VectorAssembler(inputCols=schema["features"],
                        outputCol="rawFeatures", handleInvalid="skip"),
        ChiSqSelector(mesh=mesh, numTopFeatures=cfg["numTopFeatures"],
                      featuresCol="rawFeatures", labelCol="label",
                      outputCol="features"),
        RandomForestClassifier(
            mesh=mesh, numTrees=cfg["numTrees"], maxDepth=cfg["maxDepth"],
            maxBins=cfg["maxBins"], impurity=cfg["impurity"],
            bootstrap=cfg["bootstrap"], seed=seed, featuresCol="features",
        ),
    ])


#: the forest's own device programs (``RandomForestClassifier._fit``): the
#: binning of the selected matrix, the bagging statistics, the level-wise
#: grower.  The chi-square stage's contingency (``jit_agg``) is the feature
#: layer's and is not among them.
PROGRAMS = {"fit": r"^jit_(bin_features|_one_hot_stats|_grow_fused)$"}


def work_fit(cfg, rows, info):
    """The forest's fit: the selected matrix is read once to be binned; every
    tree level reads its bin ids once (a byte each: 32 bins) with the row's
    label, weight and node (12 bytes), and adds one weight per (row,
    feature)."""
    top = cfg["numTopFeatures"]
    levels = cfg["numTrees"] * cfg["maxDepth"]
    return {"flops": 1.0 * rows * top * levels,
            "bytes": 4.0 * rows * top + levels * rows * (top + 12.0)}


def work_tree_hist(cfg, rows):
    """The histograms alone (what the ``tree_hist`` kernel has to build)."""
    top = cfg["numTopFeatures"]
    levels = cfg["numTrees"] * cfg["maxDepth"]
    return {"flops": 1.0 * rows * top * levels,
            "bytes": levels * rows * (top + 12.0)}


def pass_info(kind, last):
    return {}


def extract_product(kind, last):
    stages = last["model"].getStages()
    forest = stages[-1].forest
    return {
        "labels": list(stages[0].labels),
        "selected": [int(i) for i in stages[2].selected_features],
        "feature": np.asarray(forest.feature),
        "threshold": np.asarray(forest.threshold),
        "leaf_stats": np.asarray(forest.leaf_stats),
    }


def _prepared(cfg, columns, matmul="f32"):
    schema = gen.load_schema()
    vocab, y = ref.index_labels(columns[schema["label_column"]])
    X = ref.assemble(columns, schema["features"])
    selected, _ = ref.chi2_select(
        X, y, len(vocab), max_bins=cfg["chi2_maxBins"],
        top=cfg["numTopFeatures"], matmul=matmul,
    )
    return vocab, y, X, selected


def _checked_trees(cfg, seed):
    rng = np.random.default_rng([seed, 5])
    k = min(int(cfg["check_trees"]), cfg["numTrees"])
    return sorted(int(t) for t in rng.choice(cfg["numTrees"], k, replace=False))


def _forest_inputs(cfg, X_sel, y, seed, matmul="f32"):
    T, D = cfg["numTrees"], cfg["maxDepth"]
    data = ref.ForestData(X_sel, y, max_bins=cfg["maxBins"], seed=seed,
                          matmul=matmul)
    w = ref.bagging_weights(seed, T, X_sel.shape[0])
    masks = ref.feature_masks(
        seed, D, T, X_sel.shape[1], ref.forest_subset_k(X_sel.shape[1], T)
    )
    return data, w, masks


def control_product(kind, cfg, columns, seed, matmul):
    """The reference put in the program's place with features and thresholds
    compared in ``matmul`` arithmetic (bfloat16): it grows the checked
    trees itself; the other trees are left empty and never read."""
    vocab, y, X, selected = _prepared(cfg, columns, matmul)
    X_sel = np.ascontiguousarray(X[:, selected])
    T, D = cfg["numTrees"], cfg["maxDepth"]
    H = (1 << (D + 1)) - 1
    S = len(vocab)
    data, w, masks = _forest_inputs(cfg, X_sel, y, seed, matmul)
    feature = np.full((T, H), -2, np.int32)
    threshold = np.zeros((T, H), np.float32)
    leaf = np.zeros((T, H, S), np.float32)
    for t in _checked_trees(cfg, seed):
        (feature[t], threshold[t], leaf[t]), _ = ref.walk_tree(
            data, w[t], [None if m is None else m[t] for m in masks], S, D
        )
    return {"labels": vocab, "selected": selected, "feature": feature,
            "threshold": threshold, "leaf_stats": leaf}


def compare(kind, product, cfg, columns, seed):
    vocab, y, X, selected = _prepared(cfg, columns)
    labels = product["labels"]
    n_bad = sum(a != b for a, b in zip(labels, vocab)) + abs(
        len(labels) - len(vocab)
    )
    sel_bad = len(set(selected) ^ set(product["selected"]))
    out = {"label_mismatch": float(n_bad),
           "selected_mismatch": float(sel_bad)}
    X_sel = np.ascontiguousarray(X[:, selected])
    del X
    S, D = len(vocab), cfg["maxDepth"]
    data, w, masks = _forest_inputs(cfg, X_sel, y, seed)
    gain_gap = count_gap = 0.0
    shape_bad = 0
    leaf_p = np.asarray(product["leaf_stats"], np.float32)
    if leaf_p.shape[-1] != S:  # a forest over another number of classes
        shape_bad += abs(leaf_p.shape[-1] - S)
        fixed = np.zeros(leaf_p.shape[:-1] + (S,), np.float32)
        k = min(S, leaf_p.shape[-1])
        fixed[..., :k] = leaf_p[..., :k]
        leaf_p = fixed
    for t in _checked_trees(cfg, seed):
        given = (product["feature"][t], product["threshold"][t], leaf_p[t])
        _, rep = ref.walk_tree(
            data, w[t], [None if m is None else m[t] for m in masks], S, D,
            given=given,
        )
        gain_gap = max(gain_gap, rep["split_gain_gap"])
        count_gap = max(count_gap, rep["leaf_count_gap"])
        shape_bad += rep["tree_shape_mismatch"]
    out.update({"split_gain_gap": gain_gap, "leaf_count_gap": count_gap,
                "tree_shape_mismatch": float(shape_bad)})
    return out
