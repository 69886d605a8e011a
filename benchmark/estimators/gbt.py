"""Adapter of the ``gbt`` estimator kind: one-vs-rest boosted trees,
``OneVsRest(GBTClassifier)`` behind the label index and the assembler.  How
the program is built for the configuration, the work one pass needs from
shapes, and the comparison of what a timed fit produced with the plain
reference (``benchmark/reference_gbt.py``).

How ``correct`` is decided.  ``check_trees`` (class, round) pairs are drawn
from the seed, round 0 and the last round always among them.  For each, the
reference derives the margins before that round by walking the PRODUCT's
earlier trees of the class itself (its own walk, the product's tree weights),
takes the residuals, and follows the product's tree of that round level by
level, judging every node (``reference_gbt.walk_tree``).  The leaf means of a
late round therefore hold the whole chain: a program whose margins were not
the ones its trees and weights give reads a ``leaf_value_gap``.

Scales, and why they are not the issue's to the letter.  The program sums
float32 statistics and takes a right child's as parent minus left (in the
histograms under the kernel, in the last level's leaves everywhere), so a
node of 7 rows beside a sibling of 7,000 carries the large sibling's absolute
rounding: its own mean can read 3 % off and its own best split is drawn by
that noise, in a program that is sound.  Both gaps are therefore weighted by
what the node is of the tree: ``split_gain_gap`` is the gain the split fails
to reach times the node's rows, over the root's ``sum w r^2`` (the share of
the tree's sum of squares left unremoved), not the gain over the node's own
impurity; ``leaf_value_gap`` is the leaf's mean gap times the leaf's share
of the rows, over the root's rms residual (what the error moves the mean
margin by), not the gap over the leaf's own mean.
"""

from __future__ import annotations

import numpy as np

import gen
import reference as ref
import reference_gbt as rg


def build_pipeline(cfg, mesh, seed):
    from sntc_tpu.core.base import Pipeline
    from sntc_tpu.feature import StringIndexer, VectorAssembler
    from sntc_tpu.models import GBTClassifier, OneVsRest

    schema = gen.load_schema()
    return Pipeline(stages=[
        StringIndexer(inputCol=schema["label_column"], outputCol="label",
                      handleInvalid="skip"),
        VectorAssembler(inputCols=schema["features"], outputCol="features",
                        handleInvalid="skip"),
        OneVsRest(
            classifier=GBTClassifier(
                mesh=mesh, maxIter=cfg["maxIter"], maxDepth=cfg["maxDepth"],
                maxBins=cfg["maxBins"], stepSize=cfg["stepSize"],
                subsamplingRate=cfg["subsamplingRate"],
                featureSubsetStrategy=cfg["featureSubsetStrategy"],
                minInstancesPerNode=cfg["minInstancesPerNode"],
                minInfoGain=cfg["minInfoGain"], seed=seed,
            ),
            mesh=mesh, featuresCol="features", labelCol="label",
        ),
    ])


#: the boosted fit's own device programs: the binning of the matrix, the
#: round's statistics (signed labels in round 0, residuals after), the
#: level-wise grower, the walk that gives every row its leaf's mean; ``walk``
#: is the last alone (``boost_walk_roofline``)
PROGRAMS = {
    "fit": r"^jit_(bin_features|_label_stats|_residual_stats|_grow_fused|"
           r"forest_leaf_stats)$",
    "walk": r"^jit_forest_leaf_stats$",
}


def _tree_levels(cfg):
    return cfg["classes"] * cfg["maxIter"] * cfg["maxDepth"]


def work_tree_hist(cfg, rows):
    """The histograms alone: every tree level reads its 78 bin ids once (a
    byte each: 32 bins) with the row's three statistics, weight and node
    (20 bytes), and adds three statistics per (row, feature)."""
    F, levels = cfg["features"], _tree_levels(cfg)
    return {"flops": 3.0 * rows * F * levels,
            "bytes": levels * rows * (F + 20.0)}


def work_walk(cfg, rows):
    """The margin update of the whole fit: a round reads the row's 78
    features once and reads and writes 15 margins, and makes one compare a
    (class tree, level)."""
    K, rounds = cfg["classes"], cfg["maxIter"]
    return {"flops": 1.0 * rows * K * cfg["maxDepth"] * rounds,
            "bytes": rounds * rows * (4.0 * cfg["features"] + 8.0 * K)}


def work_fit(cfg, rows, info):
    """The fit's own programs: the matrix read once to be binned, the
    histograms, the walk, and a round's residual pass (reads 15 signed
    labels and margins, writes 15 x 3 statistics; a dozen operations each)."""
    K, rounds, F = cfg["classes"], cfg["maxIter"], cfg["features"]
    hist, walk = work_tree_hist(cfg, rows), work_walk(cfg, rows)
    return {
        "flops": hist["flops"] + walk["flops"] + 12.0 * rows * K * rounds,
        "bytes": (4.0 * rows * F + hist["bytes"] + walk["bytes"]
                  + rounds * rows * K * 20.0),
    }


def pass_info(kind, last):
    return {}


def extract_product(kind, last):
    stages = last["model"].getStages()
    models = stages[-1].models
    return {
        "labels": list(stages[0].labels),
        "feature": np.stack([np.asarray(m.forest.feature) for m in models]),
        "threshold": np.stack(
            [np.asarray(m.forest.threshold) for m in models]),
        "leaf_stats": np.stack(
            [np.asarray(m.forest.leaf_stats) for m in models]),
        "tree_weights": np.stack(
            [np.asarray(m.treeWeights, np.float32) for m in models]),
    }


def _prepared(cfg, columns, seed):
    schema = gen.load_schema()
    vocab, y = ref.index_labels(columns[schema["label_column"]])
    X = ref.assemble(columns, schema["features"])
    return vocab, rg.BoostData(X, y, max_bins=cfg["maxBins"], seed=seed)


def _checked_pairs(cfg, seed):
    """``check_trees`` (class, round) pairs from the seed: distinct rounds,
    round 0 and the last always among them, each with a class of its own
    draw."""
    rng = np.random.default_rng([seed, 6])
    rounds, K = cfg["maxIter"], cfg["classes"]
    k = min(int(cfg["check_trees"]), rounds)
    picked = {0, rounds - 1}
    others = [m for m in rng.permutation(rounds) if m not in picked]
    picked |= set(int(m) for m in others[:max(0, k - len(picked))])
    return [(int(rng.integers(K)), m) for m in sorted(picked)][:k]


def _weights(cfg):
    w = np.full(cfg["maxIter"], cfg["stepSize"], np.float32)
    w[0] = 1.0
    return w


def control_product(kind, cfg, columns, seed, matmul):
    """The reference put in the program's place with every statistic in
    ``matmul`` arithmetic before it is summed: for each checked pair it
    boosts the pair's class itself up to the pair's round; the other trees
    are left empty and never read."""
    vocab, data = _prepared(cfg, columns, seed)
    K, M, D = cfg["classes"], cfg["maxIter"], cfg["maxDepth"]
    H = (1 << (D + 1)) - 1
    feature = np.full((K, M, H), -2, np.int32)
    threshold = np.zeros((K, M, H), np.float32)
    leaf = np.zeros((K, M, H, 3), np.float32)
    weights = _weights(cfg)
    upto = {}
    for c, m in _checked_pairs(cfg, seed):
        upto[c] = max(upto.get(c, -1), m)
    for c, last in upto.items():
        y_parts = data.signed_labels(c)
        for m in range(last + 1):
            live = np.where(np.arange(M) < m, weights, 0.0)
            margins = data.margins(feature[c], threshold[c], leaf[c], live, D)
            (feature[c, m], threshold[c, m], leaf[c, m]), _ = rg.walk_tree(
                data, y_parts, margins, D, first=m == 0, matmul=matmul,
            )
    return {"labels": vocab, "feature": feature, "threshold": threshold,
            "leaf_stats": leaf,
            "tree_weights": np.broadcast_to(weights, (K, M)).copy()}


def compare(kind, product, cfg, columns, seed):
    vocab, data = _prepared(cfg, columns, seed)
    labels = product["labels"]
    n_bad = sum(a != b for a, b in zip(labels, vocab)) + abs(
        len(labels) - len(vocab)
    )
    K, M, D = cfg["classes"], cfg["maxIter"], cfg["maxDepth"]
    H = (1 << (D + 1)) - 1
    feature = np.asarray(product["feature"])
    threshold = np.asarray(product["threshold"], np.float32)
    leaf = np.asarray(product["leaf_stats"], np.float32)
    weights = np.asarray(product["tree_weights"], np.float32)
    out = {"label_mismatch": float(n_bad)}
    # 15 models of 20 trees as dense heaps of depth 5, weights 1, 0.1 x 19
    want = {"feature": (K, M, H), "threshold": (K, M, H),
            "leaf_stats": (K, M, H, 3), "tree_weights": (K, M)}
    got = {"feature": feature, "threshold": threshold, "leaf_stats": leaf,
           "tree_weights": weights}
    if any(got[k].shape != s for k, s in want.items()):
        out["tree_shape_mismatch"] = float(sum(
            got[k].shape != s for k, s in want.items()
        ))
        return out  # nothing to follow: the other checks stay unread
    shape_bad = int(np.sum(np.abs(weights - _weights(cfg)[None, :]) > 1e-7))
    gain_gap = count_gap = value_gap = gap_rows = 0.0
    min_leaf = np.inf
    for c, m in _checked_pairs(cfg, seed):
        live = np.where(np.arange(M) < m, weights[c], 0.0)
        margins = data.margins(feature[c], threshold[c], leaf[c], live, D)
        _, rep = rg.walk_tree(
            data, data.signed_labels(c), margins, D, first=m == 0,
            given=(feature[c, m], threshold[c, m], leaf[c, m]),
        )
        if rep["split_gain_gap"] > gain_gap:
            gain_gap, gap_rows = rep["split_gain_gap"], rep["gain_gap_rows"]
        count_gap = max(count_gap, rep["leaf_count_gap"])
        value_gap = max(value_gap, rep["leaf_value_gap"])
        shape_bad += rep["tree_shape_mismatch"]
        min_leaf = min(min_leaf, rep["min_leaf_rows"])
    out.update({"tree_shape_mismatch": float(shape_bad),
                "leaf_count_gap": count_gap, "split_gain_gap": gain_gap,
                "leaf_value_gap": value_gap, "min_leaf_rows": min_leaf,
                "gain_gap_rows": gap_rows})
    return out
