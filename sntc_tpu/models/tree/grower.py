"""Level-wise binned forest grower — the ``RandomForest.run`` analog.

Behavioral spec: SURVEY.md §2.3/§3.2 (upstream ``ml/tree/impl/RandomForest.
scala`` + ``DTStatsAggregator`` [U]): quantile-binned features, level-wise
growth with ALL trees' nodes trained per data pass, per-(node,feature,bin)
sufficient statistics reduced across partitions, split = impurity-gain
argmax, ``minInstancesPerNode``/``minInfoGain`` pruning.

TPU redesign (SURVEY.md §7.2 item 1 — static shapes over dynamic trees):

  * trees are DENSE heaps of ``2^(maxDepth+1)-1`` node slots (masked, not
    grown) — no dynamic structure anywhere;
  * the per-level histogram ``[T, nodes, F, B, S]`` is a ``segment_sum``
    over mesh-sharded rows (``lax.map`` over trees × ``lax.scan`` over
    features keeps peak memory at one ``[N]`` id vector); XLA inserts the
    ICI all-reduce — Spark's shuffle (§3.2 ⟦DRV→EXEC⟧) becomes one psum;
  * split selection is vectorized argmax on device; children of a split get
    their stats from the chosen (left, right) cumsums, so the final level
    needs no extra pass;
  * a unified stats vector ``S`` serves classification (weighted class
    counts, gini/entropy) and regression (``[w, wy, wy²]``, variance) — the
    same kernel grows RF and GBT trees.

Row routing uses bin ids (``bin <= split_bin`` goes left ⟺ ``x < edges[f,
split_bin]``) and is vector work, never a per-row gather (:func:`_route_rows`:
the level's split decisions packed into one small table, a compare-and-
select over its nodes, then over the feature rows of the transposed bin
matrix, each summed away); serving traverses on raw floats with the stored
thresholds.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sntc_tpu.obs import module_of, span
from sntc_tpu.obs.metrics import inc
from sntc_tpu.parallel.collectives import _put_sharded
from sntc_tpu.parallel.mesh import map_at, record_collective

_MODULE = module_of(__name__)


class Forest(NamedTuple):
    """Dense-heap forest. H = 2^(max_depth+1) - 1 slots per tree.

    ``feature[t, h] >= 0`` marks an internal node (split on that feature at
    ``threshold``); ``-1`` marks a leaf with ``leaf_stats[t, h]`` (class
    counts or [w, wy, wy²]); ``-2`` marks a never-created slot.
    ``gain``/``count`` are populated on internal nodes (0 elsewhere) and
    feed ``featureImportances`` (Spark ``computeFeatureImportance`` parity).
    """

    feature: np.ndarray  # [T, H] int32
    threshold: np.ndarray  # [T, H] f32
    leaf_stats: np.ndarray  # [T, H, S] f32
    max_depth: int
    gain: np.ndarray = None  # [T, H] f32
    count: np.ndarray = None  # [T, H] f32

    def feature_importances(
        self, n_features: int, per_tree_normalization: bool = True
    ) -> np.ndarray:
        """Gain×count importances — Spark ``TreeEnsembleModel.
        featureImportances`` semantics: each tree's contributions are
        normalized to sum 1 first for forests (RF), left raw for boosted
        ensembles (GBT passes ``perTreeNormalization=false`` upstream),
        then the total is normalized."""
        if self.gain is None or self.count is None:
            raise ValueError(
                "featureImportances unavailable: this model was saved "
                "without per-node split statistics (gain/count); re-fit "
                "to compute importances"
            )
        total = np.zeros(n_features, np.float64)
        for t in range(self.feature.shape[0]):
            imp = np.zeros(n_features, np.float64)
            internal = self.feature[t] >= 0
            np.add.at(
                imp,
                self.feature[t][internal],
                (self.gain[t] * self.count[t])[internal],
            )
            if per_tree_normalization:
                s = imp.sum()
                if s > 0:
                    total += imp / s
            else:
                total += imp
        s = total.sum()
        return (total / s if s > 0 else total).astype(np.float64)


def heap_offset(depth: int) -> int:
    return (1 << depth) - 1


class ForestPersistenceMixin:
    """Shared save/load payload + featureImportances for every model that
    is just a dense-heap forest plus ``_n_features`` (DT/RF, both tasks).
    Subclasses with extra identity (the classifiers' ``n_classes``)
    override ``_extra_meta``/``_from_forest``."""

    _per_tree_normalization = True  # RF semantics; GBT passes False

    def _extra_meta(self) -> dict:
        return {}

    @classmethod
    def _from_forest(cls, forest: "Forest", extra: dict):
        return cls(forest=forest, n_features=int(extra.get("n_features", 0)))

    def _save_extra(self):
        meta = {
            "max_depth": self.forest.max_depth,
            "n_features": self._n_features,
        }
        meta.update(self._extra_meta())
        return meta, {
            "feature": self.forest.feature,
            "threshold": self.forest.threshold,
            "leaf_stats": self.forest.leaf_stats,
            "gain": self.forest.gain,
            "count": self.forest.count,
        }

    @classmethod
    def _load_from(cls, params, extra, arrays):
        forest = Forest(
            arrays["feature"], arrays["threshold"], arrays["leaf_stats"],
            int(extra["max_depth"]),
            arrays.get("gain"), arrays.get("count"),
        )
        m = cls._from_forest(forest, extra)
        m.setParams(**params)
        return m

    @property
    def featureImportances(self) -> np.ndarray:
        n = self._n_features or int(self.forest.feature.max()) + 1
        return self.forest.feature_importances(
            n, per_tree_normalization=self._per_tree_normalization
        )


def make_bagging_weights(rng, bootstrap: bool, rate: float, T: int, n: int,
                         mesh):
    """Per-tree row weights, device-put sharded on the row axis — the ONE
    definition of the Spark bagging semantics (Poisson(subsamplingRate)
    with replacement; Bernoulli masks without — a documented deviation
    from Spark's exact sampling) shared by both forests."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    with span("rf.bagging", trees=T, rows=n, module=_MODULE):
        if bootstrap:
            w = rng.poisson(rate, size=(T, n)).astype(np.float32)
        elif rate < 1.0:
            w = (rng.random((T, n)) < rate).astype(np.float32)
        else:
            w = np.ones((T, n), np.float32)
    return _put_sharded(
        w, NamedSharding(mesh, P(None, mesh.axis_names[0]))
    )


class ForestDeviceMixin:
    """Lazy device-resident copies of the dense forest tensors: model
    parameters upload once per process, not once per serving micro-batch
    (each upload is a host→device transfer on the [B:11] hot path).
    Subclasses override ``_forest_arrays`` to add extra tensors (GBT's
    tree weights)."""

    _dev_forest = None

    def _forest_arrays(self) -> tuple:
        f = self.forest
        return (f.feature, f.threshold, f.leaf_stats)

    def _device_forest(self) -> tuple:
        forest = self._dev_forest
        if forest is None:
            forest = tuple(
                jnp.asarray(a) for a in self._forest_arrays()
            )
            # never cache values created under an active trace: the
            # fusion planner jits THROUGH _predict_all_dev, so inside
            # its tracing these constants are tracers — caching one
            # would poison every later trace AND the eager host-
            # fallback path with UnexpectedTracerError (the same guard
            # LogisticRegression/MLP got in r12; bites exactly when a
            # fused trace runs before the first eager transform)
            import jax

            if not any(isinstance(a, jax.core.Tracer) for a in forest):
                self._dev_forest = forest
        return forest


def resolve_feature_subset_k(strategy, n_features: int, n_trees: int,
                             is_classification: bool) -> int:
    """Spark featureSubsetStrategy semantics (SURVEY.md §2.3)."""
    if isinstance(strategy, (int, np.integer)):
        k = int(strategy)
    elif strategy == "auto":
        if n_trees == 1:
            k = n_features
        elif is_classification:
            k = int(math.ceil(math.sqrt(n_features)))
        else:
            k = max(1, n_features // 3)
    elif strategy == "all":
        k = n_features
    elif strategy == "sqrt":
        k = int(math.ceil(math.sqrt(n_features)))
    elif strategy == "log2":
        k = max(1, int(math.floor(math.log2(n_features))))
    elif strategy == "onethird":
        k = max(1, n_features // 3)
    else:
        try:
            frac = float(strategy)
        except (TypeError, ValueError):
            raise ValueError(f"unknown featureSubsetStrategy {strategy!r}")
        if not 0 < frac <= 1:
            raise ValueError(f"featureSubsetStrategy fraction {frac} not in (0,1]")
        k = max(1, int(math.ceil(frac * n_features)))
    return min(max(k, 1), n_features)


def _weighted_impurity(stats: jnp.ndarray, impurity: str) -> jnp.ndarray:
    """``weight * impurity`` for a stats vector (last axis S).

    gini:    w - Σ s²/w          entropy: Σ -s·log(s/w)
    variance: Σwy² - (Σwy)²/w   (stats = [w, wy, wy²])
    """
    if impurity in ("gini", "entropy"):
        w = stats.sum(axis=-1)
        safe_w = jnp.maximum(w, 1e-12)
        if impurity == "gini":
            return w - (stats**2).sum(axis=-1) / safe_w
        p = stats / safe_w[..., None]
        return -(jnp.where(stats > 0, stats * jnp.log(jnp.maximum(p, 1e-12)), 0.0)).sum(
            axis=-1
        )
    # variance
    w = stats[..., 0]
    safe_w = jnp.maximum(w, 1e-12)
    return stats[..., 2] - stats[..., 1] ** 2 / safe_w


def _stat_count(stats: jnp.ndarray, impurity: str) -> jnp.ndarray:
    if impurity == "variance":
        return stats[..., 0]
    return stats.sum(axis=-1)


#: bytes one node group's working set may take (histogram + cumsum +
#: left/right slices + gain tensor, about 5x the raw histogram).  Spark's
#: ``maxMemoryInMB=256`` bounds its node groups the same way [U]; 8x
#: that, HBM being roomier than a 2010s JVM heap: on the depth-10 bench
#: config 2 GiB more than halved deep-level wall-clock against 512 MiB
#: and going past it bought nothing.
_NODE_GROUP_BYTES = 2 << 30
#: a level's full histogram is kept on the device for the next level's
#: sibling subtraction only while it is no larger than this
_SIBLING_BYTES = 1 << 30


class LevelPlan(NamedTuple):
    """How one fit builds its histograms; hashable, the one static
    argument of :func:`_grow_fused` that says so."""

    hist_impl: str  # "pallas" | "segment", the same on every level
    group: int  # nodes a histogram pass (a power of two)
    keep_hists: Tuple[bool, ...]  # per level: kept for the next level


def _level_plan(T: int, F: int, n_bins: int, S: int, max_depth: int,
                mesh, per_tree_stats: bool = False) -> LevelPlan:
    """The fit's histogram decisions, made once from its shapes and what
    :func:`~sntc_tpu.ops.pallas_histogram.tree_hist_impl` observes.

    Node group: deep levels evaluate in several passes over the binned
    data instead of materializing a multi-GB ``[T, 2^d, F, B, S]``
    tensor, the memory/compute tradeoff Spark makes; the largest power
    of two (levels split evenly) whose working set fits
    ``_NODE_GROUP_BYTES``.  Under the kernel the group is cut further to
    the kernel's guard, so that every level takes it: more group passes
    cost less there than one level on the serial scatter-adds (on the
    v5e the kernel builds an 8-node level of the benchmark's forest in
    22 ms, and 128 nodes, the guard's edge, in 345 ms: PERF.md section 6,
    PR 30).

    Sibling subtraction (LightGBM-style, beyond Spark's
    ``DTStatsAggregator``): level ``d``'s full histogram is kept, and
    level ``d + 1`` histograms only left children and derives each right
    sibling as parent - left, where that pays: under the kernel, whose
    cost follows the node-axis width (a ``segment_sum`` scatter costs
    O(N) whatever the width, so there the kept histogram's traffic is
    pure overhead: 2.1x slower on CPU at the depth-10 bench shapes), with
    groups of at least a left/right pair, and while the kept histogram
    fits ``_SIBLING_BYTES``.

    Under the kernel a node group's histograms are ONE call, the level's
    ``T`` trees stacked as columns of one product (column order, tree
    block rule and the measured cost law: ``ops/pallas_histogram.py``).
    What the fit's calls will multiply is counted here from the plan, as
    the kernel's dispatch is: ``sntc_kernel_tree_hist_column_tiles_total``
    (128-column array tiles, which the kernel's time follows) and
    ``sntc_kernel_tree_hist_columns_total`` (columns among them that carry
    a term of a statistic); columns / (128 x tiles) is the array's fill.
    ``per_tree_stats`` (boosting) only decides how the columns lie.

    The kernel runs per shard of the mesh and every node-group pass sums
    its histogram over the shards (:func:`_group_hist`'s ``psum``, the
    fit's one collective).  Those are counted here too, outside the trace
    of :func:`_grow_fused`, so they count fits and not compilations:
    ``sntc_kernel_tree_hist_psum_total`` and
    ``sntc_kernel_tree_hist_psum_bytes_total`` (the summed histograms'
    bytes), both 0 on a mesh of one, and the fit's one dispatch in the
    ``sntc_collective_*`` series (``op="tree.histogram"``)."""
    # imported where used: Pallas costs a second to import, and a process
    # that grows no tree (the MLP's fit, the serve path) should not pay it
    from sntc_tpu.ops.pallas_histogram import (
        column_tiles,
        hist_fits_pallas,
        tree_hist_impl,
    )

    hist_bytes = T * F * n_bins * S * 4  # one node's histogram
    raw = max(1, _NODE_GROUP_BYTES // (5 * hist_bytes))
    group = 1 << (raw.bit_length() - 1)
    # asked at one node: a fit whose narrowest level the kernel refuses
    # takes it nowhere, and every wider level is cut to the guard below
    hist_impl = tree_hist_impl(1, n_bins, mesh)
    if hist_impl == "pallas":
        while not hist_fits_pallas(group, n_bins):
            group //= 2
    siblings = hist_impl == "pallas" and group >= 2
    keep_hists = tuple(
        siblings and d < max_depth - 1 and (hist_bytes << d) <= _SIBLING_BYTES
        for d in range(max_depth)
    )
    if hist_impl == "pallas":
        tiles = columns = passes = hist_nodes = 0
        for d in range(max_depth):
            g = min(1 << d, group)
            # a level whose parent's histogram was kept histograms the
            # left children only (:func:`_eval_node_group`)
            halved = d > 0 and keep_hists[d - 1] and g >= 2
            g_eff = g // 2 if halved else g
            call = column_tiles(T, S, per_tree_stats, g_eff)
            n_pass = (1 << d) // g  # node-group passes of the level
            tiles += n_pass * call[0]
            columns += n_pass * call[1]
            passes += n_pass
            hist_nodes += n_pass * g_eff
        inc("sntc_kernel_tree_hist_column_tiles_total", tiles)
        inc("sntc_kernel_tree_hist_columns_total", columns)
        axis = mesh.axis_names[0]
        n_shards = int(mesh.shape[axis])
        payload = hist_nodes * hist_bytes
        inc("sntc_kernel_tree_hist_psum_total",
            passes if n_shards > 1 else 0)
        inc("sntc_kernel_tree_hist_psum_bytes_total",
            payload if n_shards > 1 else 0)
        record_collective("tree.histogram", axis, n_shards, payload)
    return LevelPlan(hist_impl, group, keep_hists)


def _level_core(
    binned_t,  # [F, N] int32, row-sharded on axis 1 (pallas layout)
    row_stats,  # [N, S] f32 shared, or [T, S, N] per-tree, rows along
    #            lanes (boosting: every "tree" of a one-vs-rest round is a
    #            different binary problem over the same binned features)
    #            — row-sharded (:func:`_stats_width` tells the two apart)
    row_label,  # [N] int32 class ids or None (label-fused scatter path)
    row_weight,  # [N] f32 row weights or None (with row_label)
    w_trees,  # [T, N] f32 bagging weights, sharded on N
    node_idx,  # [T, N] int32 (-1 = inactive), sharded on N
    key,  # PRNG key for feature subsetting
    min_instances,  # f32 scalar
    min_info_gain,  # f32 scalar
    parent_hist,  # [T, n_nodes/2, F, B, S] previous level's histograms
    #             (sibling-subtraction path) or None (direct)
    stats_t,  # [S, N] / [S, T, N] f32: ``row_stats`` with the rows along
    #         lanes (:func:`_lane_dense_stats`), the pallas kernel's
    #         operand; None when the fit takes ``segment_sum``
    *,
    n_nodes: int,
    n_bins: int,
    impurity: str,
    subset_k: int,
    group: int,
    mesh=None,
    route: bool = True,
    keep_hist: bool = False,
):
    """One level's histogram + split evaluation + (optional) row routing,
    with the node axis evaluated in memory-bounded groups of ``group``
    nodes (Spark's maxMemoryInMB node-group analog; :func:`_level_plan`
    decides it once a fit, so it participates in the jit cache key).  Rows
    are routed over the WHOLE level's decision tables after the groups
    are stacked (:func:`_route_rows`), whatever the grouping.  Traced
    inside :func:`_grow_fused`'s unrolled level loop."""
    F = binned_t.shape[0]
    T = w_trees.shape[0]

    # feature subsetting drawn ONCE for the level (tiny [T, nodes, F]),
    # so the chosen subsets don't depend on how the nodes are grouped
    fmask = None
    if subset_k < F:
        r = jax.random.uniform(key, (T, n_nodes, F))
        kth = -jax.lax.top_k(-r, subset_k)[0][..., -1]  # kth smallest
        fmask = r <= kth[..., None]

    if n_nodes <= group:
        out = _eval_node_group(
            binned_t, row_stats, row_label, row_weight,
            w_trees, node_idx, fmask, min_instances, parent_hist, stats_t,
            lo=jnp.int32(0), g=n_nodes, n_bins=n_bins,
            impurity=impurity, mesh=mesh, keep_hist=keep_hist,
        )
    else:
        # groups share shapes (pow2 group divides the pow2 level), so the
        # whole level is ONE lax.map over group offsets: one trace, and
        # only one group's histogram working set live at a time
        n_groups = n_nodes // group
        los = jnp.arange(n_groups, dtype=jnp.int32) * group
        if fmask is None:
            args = los

            def one(lo_t):
                return _eval_node_group(
                    binned_t, row_stats, row_label, row_weight,
                    w_trees, node_idx, None, min_instances, parent_hist,
                    stats_t, lo=lo_t, g=group, n_bins=n_bins,
                    impurity=impurity, mesh=mesh, keep_hist=keep_hist,
                )
        else:
            fmask_g = fmask.reshape(T, n_groups, group, F).transpose(
                1, 0, 2, 3
            )
            args = (los, fmask_g)

            def one(a):
                return _eval_node_group(
                    binned_t, row_stats, row_label, row_weight,
                    w_trees, node_idx, a[1], min_instances, parent_hist,
                    stats_t, lo=a[0], g=group, n_bins=n_bins,
                    impurity=impurity, mesh=mesh, keep_hist=keep_hist,
                )

        stacked = jax.lax.map(one, args)  # each: [n_groups, T, group, ...]
        out = {
            k: jnp.moveaxis(v, 0, 1).reshape(
                (T, n_nodes) + v.shape[3:]
            )
            for k, v in stacked.items()
        }

    best_feat = out["best_feat"]
    best_bin = out["best_bin"]
    best_gain = out["best_gain"]
    parent_cnt = out["parent_count"]
    has_rows = parent_cnt > 0
    do_split = has_rows & jnp.isfinite(best_gain) & (best_gain > min_info_gain)
    # Spark treats minInfoGain=0 as "any strictly positive gain"
    do_split = do_split & (best_gain > 0)

    # ---- route rows to children (skipped at the last level) ----------------
    if route:
        new_node_idx = _route_rows(
            binned_t, node_idx, best_feat, best_bin, do_split
        )
    else:
        new_node_idx = node_idx

    res = {
        "best_feat": best_feat,
        "best_bin": best_bin,
        "best_gain": best_gain,
        "do_split": do_split,
        "has_rows": has_rows,
        "parent_stats": out["parent_stats"],
        "parent_count": parent_cnt,
        "left_stats": out["left_stats"],
        "right_stats": out["right_stats"],
        "new_node_idx": new_node_idx,
    }
    if keep_hist:
        res["hist"] = out["hist"]
    return res


def _route_rows(binned_t, node_idx, best_feat, best_bin, do_split):
    """Child node id of every row: ``2 * node + (bin of the node's split
    feature > the node's split bin)``, or -1 for a dead row (``node_idx ==
    -1``) and for a row whose node does not split.

    ``binned_t`` [F, N], ``node_idx`` [T, N]; ``best_feat`` / ``best_bin``
    / ``do_split`` are the level's [T, n_nodes] decision tables
    (``best_feat`` of a node that does not split may be negative).

    No per-row gather: on the TPU ``take_along_axis`` over [T, N] lowers to
    a serial ``kCustom`` fusion (12-28 ns an element).  The three tables
    fold into one int32 per node (``feat << 16 | bin``, -1 = no split);
    a row picks its node's entry, then its bin id among the ``F`` rows of
    ``binned_t``, each as a masked sum over the small axis — a compare, a
    select and a reduce that fuse, with nothing of [T, n_nodes, N] or
    [T, F, N] ever in memory."""
    F = binned_t.shape[0]
    n_nodes = best_feat.shape[1]
    if F > 1 << 15:
        raise ValueError(f"row routing packs the feature id in 15 bits, F={F}")
    # bin ids fit the low 16 bits: the estimators cap maxBins at 256
    packed = jnp.where(
        do_split, (best_feat.clip(0) << 16) | best_bin.clip(0), -1
    )  # [T, n_nodes] int32
    node_ids = jnp.arange(n_nodes, dtype=jnp.int32)[None, :, None]
    # +1/-1: a dead row matches no node and sums to 0, i.e. to "no split"
    sel = jnp.sum(
        jnp.where(node_idx[:, None, :] == node_ids, packed[:, :, None] + 1, 0),
        axis=1,
    ) - 1  # [T, N]
    feat_ids = jnp.arange(F, dtype=jnp.int32)[None, :, None]
    row_bins = jnp.sum(
        jnp.where((sel >> 16)[:, None, :] == feat_ids, binned_t[None], 0),
        axis=1,
    )  # [T, N]
    child = 2 * node_idx + (row_bins > (sel & 0xFFFF)).astype(jnp.int32)
    return jnp.where(sel >= 0, child, -1)


def _eval_node_group(
    binned_t, row_stats, row_label, row_weight,
    w_trees, node_idx, fmask, min_instances, parent_hist, stats_t,
    *,
    lo,  # traced int32 scalar: first node id of the group
    g: int,
    n_bins: int,
    impurity: str,
    mesh,
    keep_hist: bool,
):
    """Histogram + best-split evaluation for the ``g`` nodes starting at
    level-local offset ``lo`` (a traced scalar, so a whole level's groups
    run as one ``lax.map``); rows whose node lies outside the group are
    masked inactive (id −1), exactly like dead rows.

    With ``parent_hist`` (sibling-histogram subtraction — the
    LightGBM/XGBoost trick, absent from Spark's DTStatsAggregator): only
    the EVEN (left) children are histogrammed from rows; each odd sibling
    is ``parent − left``, since a split parent's rows partition exactly
    into its two children.  Halves the histogram width every level below
    the root — the dominant cost on the MXU one-hot path, and half the
    group passes on the segment path.  Children of non-split parents
    derive garbage (parent − 0) but are masked by ``exists_lvl`` in
    :func:`_grow_fused` before any heap write, and no row routes there."""
    F = binned_t.shape[0]
    S = _stats_width(row_stats)
    T = w_trees.shape[0]

    if parent_hist is not None and g >= 2:
        ids_even = jnp.where(
            (node_idx >= lo) & (node_idx < lo + g) & ((node_idx & 1) == 0),
            (node_idx - lo) >> 1, -1,
        )
        h_even = _group_hist(
            binned_t, row_stats, row_label, row_weight, w_trees,
            ids_even, stats_t, g_eff=g // 2, n_bins=n_bins, mesh=mesh,
        )
        par = jax.lax.dynamic_slice(
            parent_hist, (0, lo // 2, 0, 0, 0),
            (T, g // 2, F, n_bins, S),
        )
        # exact for integer-valued weights (Poisson bagging, unit rows:
        # small-int f32 sums); with a fractional weightCol the
        # subtraction carries ~1-ulp f32 rounding — same class of noise
        # as any reduction reorder.  For non-negative class-count stats
        # the clamp keeps a true-zero sibling cell from surfacing as a
        # tiny negative count/probability; variance stats ([w, wy, wy²])
        # are legitimately signed in wy, so they must NOT be clamped.
        h_odd = par - h_even
        if impurity in ("gini", "entropy"):
            h_odd = jnp.maximum(h_odd, 0.0)
        hist = jnp.stack([h_even, h_odd], axis=2).reshape(
            T, g, F, n_bins, S
        )
    else:
        ids = jnp.where(
            (node_idx >= lo) & (node_idx < lo + g), node_idx - lo, -1
        )
        hist = _group_hist(
            binned_t, row_stats, row_label, row_weight, w_trees,
            ids, stats_t, g_eff=g, n_bins=n_bins, mesh=mesh,
        )

    out = _eval_from_hist(hist, fmask, min_instances, impurity=impurity)
    if keep_hist:
        out["hist"] = hist
    return out


def _group_hist(
    binned_t, row_stats, row_label, row_weight, w_trees,
    node_idx,  # [T, N] int32 GROUP-LOCAL ids in [0, g_eff) (-1 = dead)
    stats_t,  # [S, N] / [S, T, N] f32 (the kernel's) or None
    *,
    g_eff: int,
    n_bins: int,
    mesh,
):
    """Histogram ``[T, g_eff, F, B, S]`` over pre-mapped local node ids.

    Three impls, chosen by what the caller passed: the pallas MXU
    bin-one-hot matmul over ``stats_t`` where the fit takes the kernel
    (``stats_t`` is made for it alone: :func:`_grow_fused`), else the
    label-fused scalar ``segment_sum`` (classification with shared
    one-hot stats — scatters N scalars into ``(node·B + bin)·S + label``
    instead of N×S vector rows, ~6× less scatter traffic; requires
    ``row_stats == one_hot(row_label) * row_weight[:, None]``), else the
    generic vector ``segment_sum``."""
    F = binned_t.shape[0]
    S = _stats_width(row_stats)
    T = w_trees.shape[0]
    per_tree_stats = row_stats.ndim == 3
    n_nodes = g_eff  # group-local histogram width

    # ---- histogram: [T, nodes, F, B, S] ------------------------------------
    if stats_t is not None:
        # MXU factored one-hot matmul kernel per shard, explicit psum over
        # the mesh (sntc_tpu/ops/pallas_histogram.py).  Every operand has
        # the rows along lanes: the statistics arrive transposed once a
        # fit, the trees' weights as they lie, and the kernel multiplies
        # the two on its own tile; dead rows (id -1) match no node
        from jax.sharding import PartitionSpec as P

        from sntc_tpu.ops.pallas_histogram import level_histogram_pallas

        axis = mesh.axis_names[0]
        st_spec = (
            P(None, None, axis) if stats_t.ndim == 3 else P(None, axis)
        )

        def shard_fn(bt, st, wt, ni):
            # one call a node group: the level's trees are columns of one
            # product, over one bin one-hot
            hs = level_histogram_pallas(
                bt, ni, st, wt, n_nodes=n_nodes, n_bins=n_bins
            )  # [T, F, nodes*B, S]
            # the fit's one collective, counted by :func:`_level_plan`
            with jax.named_scope("tree.histogram.psum"):
                return jax.lax.psum(hs, axis)

        hists = map_at(
            mesh, shard_fn,
            in_specs=(P(None, axis), st_spec, P(None, axis), P(None, axis)),
            out_specs=P(),
            check_vma=False,  # pallas_call outputs carry no vma metadata
            jit=False,  # rebuilt per level; an outer jit would recompile
        )(binned_t, stats_t, w_trees, node_idx)
    elif (
        row_label is not None
        and row_weight is not None
        and not per_tree_stats
    ):
        # label-fused scalar scatter: one weight per row lands directly in
        # its (node, bin, class) cell.  The scan runs over ``binned_t``
        # rows so each feature's bins are a CONTIGUOUS [N] slab (a
        # ``binned[:, f]`` column gather is stride-F and dominated the
        # level cost on CPU: 2.0 s → 0.70 s at the depth-10 bench shapes)
        def hist_one_scalar(w_t, node_t):
            wv = jnp.where(node_t >= 0, w_t * row_weight, 0.0)
            base = (
                jnp.where(node_t >= 0, node_t, 0) * (n_bins * S) + row_label
            )

            def per_feature(carry, col):
                h = jax.ops.segment_sum(
                    wv, base + col * S, num_segments=n_nodes * n_bins * S
                )
                return carry, h.reshape(n_nodes * n_bins, S)

            _, hists = jax.lax.scan(per_feature, 0, binned_t)
            return hists  # [F, nodes*B, S]

        hists = jax.lax.map(
            lambda args: hist_one_scalar(*args), (w_trees, node_idx)
        )  # [T, F, nodes*B, S]
    else:
        def hist_one(w_t, node_t, rs_t):
            active = (node_t >= 0).astype(rs_t.dtype)
            ids = jnp.where(node_t >= 0, node_t, 0)
            if per_tree_stats:  # [S, N]: a tree's own, rows along lanes
                rs_t = rs_t.T
            data = rs_t * (w_t * active)[:, None]

            def per_feature(carry, col):
                seg = ids * n_bins + col
                h = jax.ops.segment_sum(
                    data, seg, num_segments=n_nodes * n_bins
                )
                return carry, h

            _, hists = jax.lax.scan(per_feature, 0, binned_t)
            return hists  # [F, nodes*B, S]

        if per_tree_stats:
            hists = jax.lax.map(
                lambda args: hist_one(*args), (w_trees, node_idx, row_stats)
            )
        else:
            hists = jax.lax.map(
                lambda args: hist_one(args[0], args[1], row_stats),
                (w_trees, node_idx),
            )  # [T, F, nodes*B, S]
    return hists.reshape(T, F, n_nodes, n_bins, S).transpose(0, 2, 1, 3, 4)


def _eval_from_hist(hist, fmask, min_instances, *, impurity):
    """Best-split evaluation over a group histogram [T, g, F, B, S]."""
    T, n_nodes, F, n_bins, S = hist.shape

    # ---- split evaluation --------------------------------------------------
    cum = jnp.cumsum(hist, axis=3)  # left stats for split at bin b
    parent = cum[:, :, 0, -1, :]  # [T, nodes, S]
    left = cum[:, :, :, :-1, :]  # [T, nodes, F, B-1, S]
    right = parent[:, :, None, None, :] - left

    imp_parent = _weighted_impurity(parent, impurity)  # [T, nodes]
    gain_w = (
        imp_parent[:, :, None, None]
        - _weighted_impurity(left, impurity)
        - _weighted_impurity(right, impurity)
    )
    parent_cnt = _stat_count(parent, impurity)
    gain = gain_w / jnp.maximum(parent_cnt, 1e-12)[:, :, None, None]

    valid = (
        (_stat_count(left, impurity) >= min_instances)
        & (_stat_count(right, impurity) >= min_instances)
    )
    if fmask is not None:  # per-(tree,node) feature subset, level-drawn
        valid = valid & fmask[:, :, :, None]
    gain = jnp.where(valid, gain, -jnp.inf)

    flat = gain.reshape(T, n_nodes, F * (n_bins - 1))
    best = jnp.argmax(flat, axis=2)
    best_gain = jnp.take_along_axis(flat, best[..., None], axis=2)[..., 0]
    best_feat = (best // (n_bins - 1)).astype(jnp.int32)
    best_bin = (best % (n_bins - 1)).astype(jnp.int32)

    # children stats of the chosen split (used directly at the last level)
    bf = best_feat[..., None, None, None]
    take_f = jnp.take_along_axis(left, bf.clip(0), axis=2)[:, :, 0]  # [T,nodes,B-1,S]
    bl = jnp.take_along_axis(
        take_f, best_bin[..., None, None].clip(0), axis=2
    )[:, :, 0]  # [T, nodes, S]
    br = parent - bl

    return {
        "best_feat": best_feat,
        "best_bin": best_bin,
        "best_gain": best_gain,
        "parent_stats": parent,
        "parent_count": parent_cnt,
        "left_stats": bl,
        "right_stats": br,
    }


def _stats_width(row_stats) -> int:
    """``S`` of the grower's two statistics layouts: shared ``[N, S]``
    (one array for every tree: the forests' weighted one-hot labels), or
    per-tree ``[T, S, N]`` with the rows along lanes (boosting).  A
    per-tree array never has ``S`` minor: ``[T, N, 3]`` lies tiled to 128
    lanes in HBM, 42 times its bytes."""
    return row_stats.shape[-1 if row_stats.ndim == 2 else -2]


def _lane_dense_stats(row_stats):
    """``row_stats`` as the pallas kernel takes it, the rows along lanes:
    shared ``[N, S]`` statistics transposed to ``[S, N]`` (``[N, 15]``
    lies tiled to 128 lanes in HBM, eight times its bytes; this lies
    dense), per-tree ``[T, S, N]`` ones with the statistic outermost,
    ``[S, T, N]``, so that one statistic of a block of trees is whole
    sublane tiles (the kernel stacks the trees of a level as columns of
    one product).  One tree's own statistics are shared ones.  Made once
    a fit; the kernel reads past the last statistic or tree of a block
    itself, so nothing is padded here."""
    if row_stats.ndim == 2:
        return row_stats.T
    if row_stats.shape[0] == 1:
        return row_stats[0]
    return jnp.swapaxes(row_stats, 0, 1)


@jax.jit
def _root_stats(row_stats, w_trees):
    if row_stats.ndim == 3:
        return jnp.einsum("tn,tsn->ts", w_trees, row_stats)
    return jnp.einsum("tn,ns->ts", w_trees, row_stats)


def grow_forest(
    binned,  # [N, F] int32 (device, row-sharded)
    row_stats,  # [N, S] shared or [T, S, N] per-tree f32 (device, row-sharded)
    w_trees,  # [T, N] f32 (device, sharded on N axis=1)
    edges: np.ndarray,  # [F, B-1] host bin thresholds
    *,
    n_bins: int,
    max_depth: int,
    min_instances_per_node: float,
    min_info_gain: float,
    subset_k: int,
    impurity: str,
    seed: int,
    mesh=None,
    row_label=None,  # [N] int32 (device, row-sharded): class ids
    row_weight=None,  # [N] f32 (device, row-sharded): per-row weights
) -> Forest:
    """Grow T trees level-synchronously; returns host-side dense heaps.

    How the histograms are built (the Pallas kernel or ``segment_sum``,
    the node group, sibling subtraction) is :func:`_level_plan`'s to say,
    once a fit, from the shapes, ``mesh`` (the kernel requires one) and
    the backend.

    ``row_label``/``row_weight``: classification callers whose
    ``row_stats`` satisfy ``one_hot(row_label) * row_weight[:, None]``
    pass both to unlock the label-fused scalar scatter (~6× less scatter
    traffic than the [N, S] vector scatter) where the fit takes
    ``segment_sum``.
    """
    # every histogram impl scans the transposed layout: contiguous
    # per-feature bins (pallas lane layout; stride-F column gathers
    # dominated CPU level cost otherwise)
    binned_t = jnp.transpose(binned)
    T = w_trees.shape[0]
    S = _stats_width(row_stats)
    H = (1 << (max_depth + 1)) - 1

    if max_depth == 0:
        feature = np.full((T, H), -2, np.int32)
        threshold = np.zeros((T, H), np.float32)
        leaf_stats = np.zeros((T, H, S), np.float32)
        stats = np.asarray(_root_stats(row_stats, w_trees))
        feature[:, 0] = -1
        leaf_stats[:, 0] = stats
        return Forest(feature, threshold, leaf_stats, max_depth,
                      np.zeros((T, H), np.float32), np.zeros((T, H), np.float32))

    plan = _level_plan(
        T, binned.shape[1], n_bins, S, max_depth, mesh, row_stats.ndim == 3
    )
    keys = jax.random.split(jax.random.PRNGKey(seed), max_depth)
    if row_label is not None:
        # out-of-range labels (e.g. a -1 sentinel) must contribute ZERO,
        # exactly like one_hot's out-of-range zero vector — a raw scatter
        # of `label - 1`-style indices would corrupt a neighboring cell
        row_label = row_label.astype(jnp.int32)
        in_range = (row_label >= 0) & (row_label < S)
        row_label = jnp.clip(row_label, 0, S - 1)
        if row_weight is not None:
            row_weight = jnp.where(in_range, row_weight, 0.0)
    out = _grow_fused(
        binned_t, row_stats, row_label, row_weight, w_trees,
        jnp.asarray(edges), keys,
        jnp.float32(min_instances_per_node), jnp.float32(min_info_gain),
        max_depth=max_depth, n_bins=n_bins, impurity=impurity,
        subset_k=subset_k, plan=plan, mesh=mesh,
    )
    with span("d2h.fetch", what="forest", module=_MODULE):
        feature, threshold, leaf_stats, gain_arr, count_arr = (
            np.asarray(a) for a in out
        )
    return Forest(feature, threshold, leaf_stats, max_depth,
                  gain_arr, count_arr)


@partial(
    jax.jit,
    static_argnames=(
        "max_depth", "n_bins", "impurity", "subset_k", "plan", "mesh",
    ),
)
def _grow_fused(
    binned_t, row_stats, row_label, row_weight, w_trees,
    edges_dev, keys,
    min_instances, min_info_gain,
    *, max_depth, n_bins, impurity, subset_k, plan, mesh,
):
    """The WHOLE level-wise growth as one XLA program: the depth loop is
    unrolled at trace time, so every level keeps its exact node count
    (``2^d`` — no padding waste) and heap updates are static slices.  No
    host round trip per level — the forest leaves the device exactly once
    (SURVEY.md §1 restack: the per-level driver synchronization of Spark's
    ``while nodeStack`` loop disappears entirely).  Between levels the rows'
    node ids ``[T, N]`` are rewritten by :func:`_route_rows` (compare-and-
    select over the level's decision tables and the rows of ``binned_t``;
    the ``[N, F]`` bin matrix is no operand of this program)."""
    T, n = w_trees.shape
    S = _stats_width(row_stats)
    H = (1 << (max_depth + 1)) - 1

    feature = jnp.full((T, H), -2, jnp.int32)
    threshold = jnp.zeros((T, H), jnp.float32)
    leaf_stats = jnp.zeros((T, H, S), jnp.float32)
    gain_a = jnp.zeros((T, H), jnp.float32)
    count_a = jnp.zeros((T, H), jnp.float32)
    node_idx = jnp.zeros((T, n), jnp.int32)
    exists_lvl = jnp.ones((T, 1), bool)  # root exists

    # the kernel's statistics operand, made once a fit and shared by every
    # level (and, for shared statistics, by every tree)
    stats_t = (
        _lane_dense_stats(row_stats) if plan.hist_impl == "pallas" else None
    )

    prev_hist = None
    for depth in range(max_depth):
        n_nodes = 1 << depth
        off = n_nodes - 1
        out = _level_core(
            binned_t, row_stats, row_label, row_weight,
            w_trees, node_idx, keys[depth],
            min_instances, min_info_gain, prev_hist, stats_t,
            n_nodes=n_nodes, n_bins=n_bins, impurity=impurity,
            subset_k=subset_k, group=plan.group, mesh=mesh,
            route=depth < max_depth - 1,
            keep_hist=plan.keep_hists[depth],
        )
        prev_hist = out.get("hist")
        split_mask = out["do_split"] & exists_lvl
        leaf_mask = exists_lvl & ~split_mask

        lvl = slice(off, off + n_nodes)
        bf_c, bb_c = out["best_feat"].clip(0), out["best_bin"].clip(0)
        feature = feature.at[:, lvl].set(
            jnp.where(split_mask, out["best_feat"],
                      jnp.where(exists_lvl, -1, -2))
        )
        threshold = threshold.at[:, lvl].set(
            jnp.where(split_mask, edges_dev[bf_c, bb_c], 0.0)
        )
        leaf_stats = leaf_stats.at[:, lvl, :].set(
            jnp.where(leaf_mask[..., None], out["parent_stats"], 0.0)
        )
        gain_a = gain_a.at[:, lvl].set(
            jnp.where(split_mask, out["best_gain"], 0.0)
        )
        count_a = count_a.at[:, lvl].set(
            jnp.where(split_mask, out["parent_count"], 0.0)
        )

        # children written as leaves with the chosen split's child stats;
        # the next (deeper) level overwrites its whole slice, re-deciding
        # which of them split further
        child_exists = jnp.repeat(split_mask, 2, axis=1)  # [T, 2*n_nodes]
        child_stats = jnp.stack(
            [out["left_stats"], out["right_stats"]], axis=2
        ).reshape(T, 2 * n_nodes, S)
        lvl2 = slice(off + n_nodes, off + 3 * n_nodes)
        feature = feature.at[:, lvl2].set(
            jnp.where(child_exists, -1, -2)
        )
        leaf_stats = leaf_stats.at[:, lvl2, :].set(
            jnp.where(child_exists[..., None], child_stats, 0.0)
        )

        exists_lvl = child_exists
        if depth < max_depth - 1:
            node_idx = out["new_node_idx"]

    return feature, threshold, leaf_stats, gain_a, count_a


@partial(jax.jit, static_argnames=("max_depth", "value"))
def forest_leaf_stats(X, feature, threshold, leaf_stats, *, max_depth: int,
                      value: bool = False):
    """Route each row down each tree on raw floats (``x >= threshold``
    goes right, NaN left) and return its leaf's stats ``[T, N, S]``, or,
    with ``value`` (what boosting asks for), the leaf's mean ``[T, N]``:
    ``sum / max(count, 1e-12)`` of ``[w, wy, wy²]`` stats, divided on the
    ``[T, H]`` leaf table first, so that no ``[T, N, S]`` array is built.

    The one walk of the package: the boosting loops' margin update, the
    models' ``transform`` and the serve kernel's XLA twin.  No per-row
    gather, as in :func:`_route_rows` (a per-row table lookup over [T, N]
    lowers to a serial ``kCustom`` fusion on the TPU, 12-28 ns an
    element): a row picks its node's feature id and threshold among the
    ``2^d`` entries of its level, its feature value among the ``F`` rows
    of ``X`` transposed, and at the end its leaf among the ``H`` slots,
    each as a masked sum over the small axis with one term that is not
    zero, so every picked integer and float is the table's own.  Dense
    and level-synchronous: no data-dependent control flow (SURVEY.md §1
    restack: "no dynamic DAG")."""
    T, H = feature.shape
    F = X.shape[1]
    X_t = X.T  # [F, N]: rows along lanes, as the grower's ``binned_t``
    feat_ids = jnp.arange(F, dtype=jnp.int32)[None, :, None]
    node = jnp.zeros((T, X.shape[0]), jnp.int32)  # heap slot of each row
    for depth in range(max_depth):
        off, n_nodes = (1 << depth) - 1, 1 << depth
        # a row resting in a shallower leaf matches no node of the level
        at = (node - off)[:, None, :] == jnp.arange(
            n_nodes, dtype=jnp.int32
        )[None, :, None]  # [T, n_nodes, N]
        feat_d, thr_d = (
            jax.lax.slice_in_dim(a, off, off + n_nodes, axis=1)[..., None]
            for a in (feature, threshold)
        )  # [T, n_nodes, 1]
        # +1/-1: no match sums to -1, a leaf's id (-1, -2) stays negative
        f = jnp.sum(jnp.where(at, feat_d + 1, 0), axis=1) - 1  # [T, N]
        thr = jnp.sum(jnp.where(at, thr_d, 0.0), axis=1)
        xv = jnp.sum(
            jnp.where(f[:, None, :] == feat_ids, X_t[None], 0.0), axis=1
        )  # [T, N]
        child = 2 * node + 1 + (xv >= thr).astype(jnp.int32)
        node = jnp.where(f >= 0, child, node)
    at = node[:, None, :] == jnp.arange(H, dtype=jnp.int32)[None, :, None]
    if value:
        means = leaf_stats[..., 1] / jnp.maximum(leaf_stats[..., 0], 1e-12)
        return jnp.sum(jnp.where(at, means[..., None], 0.0), axis=1)
    return jnp.sum(
        jnp.where(at[..., None], jnp.expand_dims(leaf_stats, 2), 0.0), axis=1
    )  # [T, N, S]
