"""Pallas TPU kernel: per-level tree histogram as MXU matmuls.

The tree grower's hot op (SURVEY.md §3.2/§7.2 item 1) is the
(tree, node, feature, bin, stat) sufficient-statistics accumulation

    hist[t, f, node, bin, s] = sum_n [node_t,n == node] * [bin_n,f == bin]
                                     * w_t,n * stats[(t,) n, s]

The XLA fallback (sntc_tpu/ops/histogram.py + grower) lowers it to
scatter-adds, which serialize on TPU.  This kernel writes it as one
matmul per row tile whose two operands carry one indicator each, with
every tree of the level among the columns:

    x_p   = w * stats                    # a piece: [rows_p, TILE] float32
    A_t   = [(node == k) ? term(x_p) : 0 # [3 * pieces * NODES * rows_p,
             for term in (hi, mid, lo)   #  TILE] bfloat16: the columns are
             for p in pieces for k]      #  (term, piece, node, row)
    onehot= [(iota_bins == bins[f]) for f]        # [F_blk * B_pad, TILE]
    acc  += onehot . A_t^T                        # contract the rows

The bin one-hot is the costly streamed operand (``F * n_bins`` rows a
row tile) and does not depend on the tree: it is built once a grid step
and multiplied once against the columns of as many trees and nodes as
the step holds.  The node is folded into the statistics (dead rows, id
-1, match no node and add nothing); only the bin is one-hot per feature,
as ``binned_t`` lies, built in bfloat16, where 0 and 1 are exact.

What a piece is decides how full the columns are (``_stat_major``).
Tree-major: a piece is one tree's statistics, ``S`` padded to 8 sublane
rows, columns (term, tree, node, statistic): 3 x 16 columns a tree-node
at the forests' 15 classes, of which 45 carry a statistic, but 3 x 8 at
the boosted trees' 3, of which 9 do.  Stat-major: a piece is ONE
statistic of a block of trees (``weight [T_blk, TILE]`` times the
statistic's row, shared, or its ``[T_blk, TILE]`` slab of per-tree
statistics laid ``[S, T, N]``), columns (term, statistic, node, tree):
3 x 3 x 16 columns a node for 15 trees, 135 of 144 live.  Every piece
is whole sublane tiles either way, so ``A_t`` is concatenated without a
relayout.  The form with fewer columns is taken; per-tree statistics
are always stat-major (that is how they lie).

Cost law (v5e, one chip, N = 4,063,232 rows; my chip runs, PRs 30, 33
and 34, ``PERF.md`` section 6): the kernel is MXU-bound on whole
128-column array tiles, about 7.0 ms a tile at 40 features x 32 bins
and 14.6 ms at 78 (2 x 2,496 one-hot rows x N x 128 columns = 2.60
TFLOP, 13.2 ms at the 197 TFLOP/s peak), whatever the columns hold.
So ``_column_plan`` picks the trees and the nodes of a step to issue
the fewest tiles: a tree block is every tree or a multiple of 8, a
step's columns are at most ``_MAX_COLUMNS`` and, unless the step is the
whole level, rounded up to whole 128-lane tiles with zero columns that
the wrapper drops.  A tree count that does not fill the last block
reads rows past the arrays' end; the kernel gives them node id -1.
``column_tiles`` is the same count for the grower's counters
(``sntc_kernel_tree_hist_column_tiles_total`` / ``..._columns_total``).

Precision.  The statistics are float32 and the product is exact to
float32: ``x`` is split into its three bfloat16 terms, ``hi = bf16(x)``,
``mid = bf16(x - hi)``, ``lo = bf16(x - hi - mid)`` (8 + 8 + 8 mantissa
bits, so ``hi + mid + lo == x`` bit for bit for every float32 whose low
term does not underflow), the terms are stacked as columns of ``A_t`` and
each is multiplied ONCE, in one default-precision pass with float32
accumulation; the wrapper adds the three partial histograms in float32.
That is what the HIGHEST matmul precision computes for this product,
minus the three of its six passes that multiply the one-hot's middle and
low terms, which are all zeros: every product here is a 0/1 factor times
a bfloat16 term, hence exact, and the sums are float32, over the row
tiles in order, a column at a time: stacking trees changes no tree's
sums.  Integer-valued weighted statistics with cell sums under 2^24
come out array-equal to the ``segment_sum`` twin.

Layouts: every operand has the rows along lanes: ``binned_t`` ``[F, N]``,
the statistics ``[S, N]`` or ``[S, T, N]`` (made once a fit by the
grower), the node ids and the trees' weights ``[T, N]``.  The row-tile
axis is the innermost grid dimension; the output block ``[F_blk * B_pad,
columns]`` is revisited across row tiles and accumulated in place
(initialized at r == 0), the standard Pallas reduction pattern; tree
blocks and node chunks are grid axes of the same product.

Selection is :func:`tree_hist_impl`, the one place that knows the rule and
the one reader of ``SNTC_TREE_HIST``; the grower, ``ChiSqSelector`` and
``stat.ChiSquareTest`` ask it once a fit.  Off a TPU the kernel runs
through the Pallas interpreter (:func:`level_histogram_pallas` decides
that itself), which backs the CPU tests.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sntc_tpu.obs.metrics import inc


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


_F_BLOCK = 8  # least features per grid step (TPU sublane granularity)
_ONEHOT_BUDGET = 4 * 1024 * 1024  # the guard's measure of a level's width
_MIN_TILE = 128
_MAX_TILE = 2048
_MAX_COLUMNS = 2048  # stacked columns (terms x trees x nodes x rows) a step
# the accumulator block [F_blk * B_pad, cols]: with every feature of either
# benchmark cell in one step up to 1,920 (40 features) / 1,152 (78) columns,
# so the one-hot is built once a column chunk and ``A_t`` once a row tile
_ACC_BUDGET = 12 * 1024 * 1024
# sized for the v5e's 128 MiB of VMEM a core (the chip this kernel is
# measured on): three quarters of it as the scoped limit (the accumulator
# block twice, once more for a step's contribution), and a row tile's
# values under that.  A chip with less has to lower both or the call
# will not compile there.
_TILE_BUDGET = 36 * 1024 * 1024  # one-hot + A_t values of one row tile
_VMEM_LIMIT = 96 * 1024 * 1024
#: contract the row (lane) axis of both operands: the ``q . k^T`` form
_CONTRACT_ROWS = (((1,), (1,)), ((), ()))


def hist_fits_pallas(n_nodes: int, n_bins: int) -> bool:
    """True if a level histogram of this width takes the kernel (beyond
    it callers fall back to the segment_sum impl).  The line is where the
    fused node x bin one-hot of the kernel's first form overflowed VMEM at
    the minimum row tile.  The kernel has not built that one-hot since
    PR 30 and needs less VMEM at every width the line admits (``_plan``),
    but the grower's node groups are cut to this line, so moving it onto
    ``_plan`` would change which program a forest of 256+ nodes x 32 bins
    compiles, and no benchmark cell grows one to judge that by: the
    verdicts stay until one does (``ROADMAP.md`` C6)."""
    nb_pad = _round_up(max(n_nodes * n_bins + 1, 128), 128)
    return _MIN_TILE * nb_pad * 4 <= _ONEHOT_BUDGET


def tree_hist_impl(n_nodes: int, n_bins: int, mesh) -> str:
    """``"pallas"`` or ``"segment"``: which implementation builds a fit's
    histograms of up to ``n_nodes`` nodes x ``n_bins`` bins a pass.

    The kernel where the backend is a TPU, a mesh is given (the kernel
    runs per shard under :func:`~sntc_tpu.parallel.mesh.map_at`) and the
    guard admits the width; the XLA ``segment_sum`` elsewhere.  On the
    v5e the kernel is the faster by far: XLA lowers the scatter-adds to
    serial loops there, and the five kernel calls of the benchmark's
    forest fit (4,063,232 rows x 40 features, 1-8 histogrammed nodes)
    take 1.149 s of a 12.873 s fit, the chi-square contingency 0.0146 s
    (ledger, PR 30, ``breakdown.device_ops``).

    ``SNTC_TREE_HIST`` = ``pallas`` | ``segment`` stands in for the
    backend's verdict (mesh and guard still apply).  It is the one switch
    the fit's histogram keeps, for its two users: ``pallas`` off a TPU is
    how ``chip_smoke.py --rehearse-cpu`` and the twin-comparison tests
    run the kernel (through the interpreter), and ``segment`` on a TPU is
    the operator's way back to the XLA twin.

    Every call counts into ``sntc_kernel_dispatch_total{kernel=
    "tree_hist",impl="pallas"}`` or ``sntc_kernel_fallback_total{kernel=
    "tree_hist",reason="segment"}``."""
    want = os.environ.get("SNTC_TREE_HIST") or (
        "pallas" if jax.default_backend() == "tpu" else "segment"
    )
    if (
        want == "pallas"
        and mesh is not None
        and hist_fits_pallas(n_nodes, n_bins)
    ):
        inc("sntc_kernel_dispatch_total", kernel="tree_hist", impl="pallas")
        return "pallas"
    inc("sntc_kernel_fallback_total", kernel="tree_hist", reason="segment")
    return "segment"


def _split3(x):
    """The three bfloat16 terms of a float32 array, each still float32:
    ``hi + mid + lo == x`` exactly (the two differences are exact in
    float32), and each term converts to bfloat16 without rounding."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    rest = x - hi
    mid = rest.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, mid, rest - mid


class _Plan(NamedTuple):
    """One call's blocks, from its static shapes alone (:func:`_plan`)."""

    stat_major: bool  # a piece of ``A_t`` is one statistic of the step's
    #                   trees (else one tree's ``S_pad`` statistics)
    tree_block: int  # trees a grid step
    node_chunk: int  # nodes a grid step
    cols: int  # stacked columns a step (the product's width)
    f_block: int  # features a step
    tile_n: int  # rows a step


def _stat_major(n_trees: int, n_stats: int, per_tree: bool) -> bool:
    """Which axis of ``weight * stats`` lies along the sublanes of a piece
    of ``A_t``.  Tree-major (a piece is one tree's statistics, ``S``
    padded to 8 rows) pads every tree-node to ``round_up(S, 8)`` columns a
    term; stat-major (a piece is one statistic of a block of trees) pads a
    block of trees to ``round_up(T, 8)``.  The one with fewer columns;
    per-tree statistics lie ``[S, T, N]``, which only stat-major reads
    without a relayout (one tree's own statistics are shared ones)."""
    return (per_tree and n_trees > 1) or (
        n_stats * _round_up(n_trees, 8) < n_trees * _round_up(n_stats, 8)
    )


def _column_plan(n_trees: int, n_stats: int, per_tree: bool, n_nodes: int):
    """``(stat_major, tree_block, node_chunk, cols, tiles)``: the trees
    and nodes of a grid step, so that the level issues the fewest
    128-column array tiles (the kernel's cost law; ``tiles`` is their
    count) with at most ``_MAX_COLUMNS`` columns a step, and of those the
    fewest steps.  A tree block is every tree or a
    multiple of 8 (the node ids and weights are blocked ``[tree_block,
    tile]``); a step's columns are rounded up to whole 128-lane tiles with
    zero columns unless the step is the whole level (a block that is not
    the whole array has to be lane-aligned)."""
    stat_major = _stat_major(n_trees, n_stats, per_tree)
    if stat_major:
        unit = 3 * n_stats  # columns a (tree, node)
        blocks = range(8, _round_up(n_trees, 8) + 1, 8)
    else:
        unit = 3 * _round_up(n_stats, 8)
        blocks = [*range(8, n_trees, 8), n_trees]
    best = None
    for tb in blocks:
        for nc in range(1, n_nodes + 1):
            steps = -(-n_trees // tb) * -(-n_nodes // nc)
            cols = unit * tb * nc
            if steps > 1:
                cols = _round_up(cols, 128)
            if cols > _MAX_COLUMNS and (tb, nc) != (blocks[0], 1):
                break  # wider from here on; the least step always runs
            key = (steps * -(-cols // 128), steps)
            if best is None or key < best[0]:
                best = (key, tb, nc, cols)
    return (stat_major,) + best[1:] + best[0][:1]


def column_tiles(n_trees: int, n_stats: int, per_tree: bool, n_nodes: int):
    """``(tiles, columns)`` of one call: the 128-column array tiles its
    products issue, and the columns among them that carry a term of a
    statistic (3 terms x trees x nodes x statistics).  What the grower
    counts into ``sntc_kernel_tree_hist_column_tiles_total`` /
    ``sntc_kernel_tree_hist_columns_total``: the kernel's time follows
    the first (the MXU multiplies whole tiles), the work the second."""
    tiles = _column_plan(n_trees, n_stats, per_tree, n_nodes)[-1]
    return tiles, 3 * n_trees * n_nodes * n_stats


def _hist_kernel(
    binned_ref, node_ref, weight_ref, stats_ref, acc_ref,
    *, b_pad, n_trees, n_stats, per_tree, plan,
):
    t_blk, c, r = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    tile = binned_ref.shape[1]
    nodes = node_ref[...] - c * plan.node_chunk  # [T_blk, TILE] chunk-local
    if n_trees % plan.tree_block:
        # the last block's spare trees (rows past the arrays' end, whatever
        # they hold) match no node
        tree = t_blk * plan.tree_block + jax.lax.broadcasted_iota(
            jnp.int32, nodes.shape, 0
        )
        nodes = jnp.where(tree < n_trees, nodes, -1)
    # a piece: float32 rows of weight * stats, and the node id of each
    if plan.stat_major:
        w = weight_ref[...]
        pieces = [
            (w * (stats_ref[s] if per_tree else stats_ref[s:s + 1, :]), nodes)
            for s in range(n_stats)
        ]
    else:
        stats = stats_ref[...]  # [S_pad, TILE], shared by the trees
        pieces = [
            (weight_ref[t:t + 1, :] * stats, nodes[t:t + 1, :])
            for t in range(plan.tree_block)
        ]
    # the fold is a select, so it commutes with the split: split a piece
    # once, select its terms into every node's rows
    split = [
        (_split3(x), [ids == k for k in range(plan.node_chunk)])
        for x, ids in pieces
    ]
    terms = [
        jnp.where(m, ts[j], 0.0)
        for j in range(3) for ts, in_node in split for m in in_node
    ]
    spare = plan.cols - sum(t.shape[0] for t in terms)
    if spare:  # zero columns up to the block
        terms.append(jnp.zeros((spare, tile), jnp.float32))
    a_t = jnp.concatenate(terms, axis=0).astype(jnp.bfloat16)  # exact
    f_block = binned_ref.shape[0]
    bin_ids = jax.lax.broadcasted_iota(jnp.int32, (b_pad, tile), 0)
    onehot = jnp.concatenate(
        [
            (bin_ids == binned_ref[j:j + 1, :]).astype(jnp.bfloat16)
            for j in range(f_block)
        ],
        axis=0,
    )  # [f_block * B_pad, TILE]
    # one default-precision pass: both operands are bfloat16 already and
    # every product is 0/1 times a bfloat16 term, summed in float32
    contrib = jax.lax.dot_general(
        onehot, a_t, _CONTRACT_ROWS, preferred_element_type=jnp.float32
    )  # [f_block * B_pad, cols]

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = contrib

    @pl.when(r != 0)
    def _acc():
        acc_ref[...] += contrib


def _plan(f: int, n: int, n_trees: int, n_stats: int, per_tree: bool,
          n_nodes: int, b_pad: int) -> _Plan:
    """Blocks from the static shapes alone: the column layout and the
    trees and nodes a step (:func:`_column_plan`), features a step (all
    of them where the accumulator block fits ``_ACC_BUDGET``, so ``A_t``
    is built once a row tile; else a multiple of 8), and the row tile:
    the largest power of two from ``_MIN_TILE`` to ``_MAX_TILE`` whose
    one-hot and ``A_t`` (10 bytes an element with their float32
    intermediates) fit ``_TILE_BUDGET``, or the largest smaller one that
    divides ``n`` if any does (a ragged tail costs a padded copy of every
    operand a call)."""
    stat_major, tree_block, node_chunk, cols, _ = _column_plan(
        n_trees, n_stats, per_tree, n_nodes
    )
    fit = _ACC_BUDGET // (b_pad * cols * 4)
    if f <= fit:
        f_block = f  # one block, as ``binned_t`` lies: no feature padding
    else:  # blocks of a sublane multiple, one that divides F if any does
        f_block = max(_F_BLOCK, fit // _F_BLOCK * _F_BLOCK)
        while f_block > _F_BLOCK and _round_up(f, _F_BLOCK) % f_block:
            f_block -= _F_BLOCK
    fits = _MAX_TILE
    while fits > _MIN_TILE and (
        10 * fits * (f_block * b_pad + cols) > _TILE_BUDGET
    ):
        fits //= 2
    tile_n = fits
    while tile_n > _MIN_TILE and n % tile_n:
        tile_n //= 2
    if n % tile_n:  # no candidate divides n: pad once, at the largest
        tile_n = fits
    return _Plan(stat_major, tree_block, node_chunk, cols, f_block, tile_n)


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "tile_n", "interpret"),
)
def level_histogram_pallas(
    binned_t: jnp.ndarray,  # [F, N] int32 (transposed bins)
    node_idx: jnp.ndarray,  # [T, N] int32 (-1 = dead row)
    stats_t: jnp.ndarray,  # [S, N] shared / [S, T, N] per-tree f32
    weight: jnp.ndarray,  # [T, N] f32 the trees' row weights
    *,
    n_nodes: int,
    n_bins: int,
    tile_n: int = None,
    interpret: bool = None,
) -> jnp.ndarray:
    """A level's histograms ``[T, F, n_nodes * n_bins, S]`` of ``weight *
    stats_t`` for all ``T`` trees at once (LOCAL rows — caller psums
    across shards).

    Every operand has the rows along lanes.  The statistics are shared by
    the trees (``[S, N]``: the forests) or each tree's own (``[S, T, N]``,
    the statistic outermost so that a statistic of a block of trees is a
    whole tile: boosting).  Grid is ``(F / F_blk, tree blocks, node
    chunks, N / tile)``, all from the static shapes (:func:`_plan`); ``N``
    a multiple of the tile is taken as it lies, else every operand is
    zero-padded here first.  ``interpret`` left ``None`` means the Pallas
    interpreter unless the backend is a TPU, so no caller has to probe;
    tests pass it to pin either.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    f, n = binned_t.shape
    t = node_idx.shape[0]
    per_tree = stats_t.ndim == 3
    if per_tree and t == 1:  # one tree's own statistics are shared ones
        stats_t, per_tree = stats_t[:, 0, :], False
    s = stats_t.shape[0]
    b_pad = _round_up(n_bins, 16)  # the bfloat16 sublane tile
    plan = _plan(f, n, t, s, per_tree, n_nodes, b_pad)
    if tile_n is None:
        tile_n = plan.tile_n
    tb, nc, cols, f_block = (
        plan.tree_block, plan.node_chunk, plan.cols, plan.f_block
    )
    t_blocks, n_chunks = -(-t // tb), -(-n_nodes // nc)
    n_pad = _round_up(n, tile_n)
    f_pad = _round_up(f, f_block)

    if n_pad != n:
        rows = ((0, 0), (0, n_pad - n))
        binned_t = jnp.pad(binned_t, rows)
        node_idx = jnp.pad(node_idx, rows, constant_values=-1)
        weight = jnp.pad(weight, rows)
        stats_t = jnp.pad(stats_t, ((0, 0),) * (stats_t.ndim - 1) + rows[1:])
    if f_pad != f:
        binned_t = jnp.pad(binned_t, ((0, f_pad - f), (0, 0)))

    # the statistics' block: every statistic of the step's trees, or the
    # shared ones whole (a block taller than the array reads rows past
    # its end, which become columns the unpacking below drops)
    if per_tree:
        stats_spec = pl.BlockSpec(
            (s, tb, tile_n), lambda i, b, c, r: (0, b, r)
        )
    else:
        stats_spec = pl.BlockSpec(
            (_round_up(s, 8), tile_n), lambda i, b, c, r: (0, r)
        )
    out = pl.pallas_call(
        functools.partial(
            _hist_kernel, b_pad=b_pad, n_trees=t, n_stats=s,
            per_tree=per_tree, plan=plan,
        ),
        grid=(f_pad // f_block, t_blocks, n_chunks, n_pad // tile_n),
        in_specs=[
            pl.BlockSpec((f_block, tile_n), lambda i, b, c, r: (i, r)),
            pl.BlockSpec((tb, tile_n), lambda i, b, c, r: (b, r)),  # nodes
            pl.BlockSpec((tb, tile_n), lambda i, b, c, r: (b, r)),  # weight
            stats_spec,
        ],
        out_specs=pl.BlockSpec(
            (f_block * b_pad, cols), lambda i, b, c, r: (i, b * n_chunks + c)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (f_pad * b_pad, t_blocks * n_chunks * cols), jnp.float32
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"
            ),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(binned_t, node_idx, weight, stats_t)

    # a step's columns are (term, piece, node, row) and its spare zeros,
    # where (piece, row) is (statistic, tree) or (tree, statistic): add
    # the three terms' partial histograms in float32, then [T, F, node,
    # bin, S] (the grower's layout)
    pieces, rows = (s, tb) if plan.stat_major else (tb, _round_up(s, 8))
    out = out.reshape(f_pad, b_pad, t_blocks, n_chunks, cols)
    out = out[..., :3 * pieces * nc * rows]
    out = out.reshape(f_pad, b_pad, t_blocks, n_chunks, 3, pieces, nc, rows)
    out = out.sum(axis=4)  # [f, bin, block, chunk, piece, node, row]
    out = out.transpose(
        (2, 6, 0, 3, 5, 1, 4) if plan.stat_major else (2, 4, 0, 3, 5, 1, 6)
    )  # [block, tree, f, chunk, node, bin, stat]
    out = out.reshape(t_blocks * tb, f_pad, n_chunks * nc, b_pad, -1)
    return out[:t, :f, :n_nodes, :n_bins, :s].reshape(
        t, f, n_nodes * n_bins, s
    )


# registered in the kernel capability registry for its guard, twin and
# smoke case (the registry <-> docs <-> tests drift check, chip_smoke.py's
# twins); selection is tree_hist_impl above, not the serve tier's switch
from sntc_tpu.kernels.registry import KernelSpec, register_kernel  # noqa: E402

def _smoke_case(rows: int):
    """One depth-4 level of the bench config 3 forest: 16 nodes x 32
    bins over the 40 chi-square-selected features, 15 class stats, with
    dead rows and real-valued weights."""
    import numpy as np

    n_nodes, n_bins, f, s = 16, 32, 40, 15
    rng = np.random.default_rng(0)
    node_idx = rng.integers(-1, n_nodes, size=rows).astype(np.int32)

    def twin(binned_t, node_idx, stats_t, weight):
        data = (stats_t * jnp.where(node_idx >= 0, weight, 0.0)).T
        ids = jnp.maximum(node_idx, 0)[None, :] * n_bins + binned_t
        return jax.vmap(
            lambda i: jax.ops.segment_sum(
                data, i, num_segments=n_nodes * n_bins
            )
        )(ids)

    def kernel(binned_t, node_idx, stats_t, weight, **kw):  # one tree
        return level_histogram_pallas(
            binned_t, node_idx[None], stats_t, weight[None],
            n_nodes=n_nodes, n_bins=n_bins, **kw,
        )[0]

    return (
        kernel,
        twin,
        (
            rng.integers(0, n_bins, size=(f, rows)).astype(np.int32),
            node_idx,
            rng.random((s, rows)).astype(np.float32),
            rng.random(rows).astype(np.float32),
        ),
        1e-5,
    )


register_kernel(
    KernelSpec(
        name="tree_hist",
        module="sntc_tpu/ops/pallas_histogram.py",
        guard_name="hist_fits_pallas",
        guard=hist_fits_pallas,
        tolerance="<=1e-5 rel f32 (three exact bf16 terms, f32 sums)",
        fallback="XLA segment_sum level histogram (ops/histogram.py)",
        env="SNTC_TREE_HIST",
        smoke_case=_smoke_case,
    )
)
