"""Pallas TPU kernel: per-level tree histogram as MXU matmuls.

The tree grower's hot op (SURVEY.md §3.2/§7.2 item 1) is the
(node, feature, bin, stat) sufficient-statistics accumulation

    hist[f, node, bin, s] = sum_n [node_n == node] * [bin_n,f == bin]
                                  * w_n * stats[n, s]

The XLA fallback (sntc_tpu/ops/histogram.py + grower) lowers it to
scatter-adds, which serialize on TPU.  This kernel writes it as one
matmul per row tile whose two operands carry one indicator each:

    x     = w * stats_t                              # [S_pad, TILE]
    A_t   = [(node == k) ? term(x) : 0               # [3 * NODES * S_pad,
             for term in (hi, mid, lo) for k]        #  TILE] bfloat16
    onehot= [(iota_bins == bins[f]) for f]           # [F_blk * B_pad, TILE]
    acc  += onehot . A_t^T                           # contract the rows

The node is folded into the statistics once per row tile (it does not
depend on the feature; dead rows, id -1, match no node and add nothing);
only the bin is one-hot per feature, as ``binned_t`` lies, ``n_bins``
wide and built in bfloat16, where 0 and 1 are exact.  ``A_t`` is the
stationary operand, shared by every feature of the step, and hundreds of
one-hot rows stream through it.

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
a bfloat16 term, hence exact, and the sums are float32.  Integer-valued
weighted statistics with cell sums under 2^24 come out array-equal to
the ``segment_sum`` twin.

Layouts: every operand has the rows along lanes: ``binned_t`` ``[F, N]``,
the statistics transposed ``[S, N]`` (made once a fit by the grower), the
node ids and the tree's weight ``[N]``.  The row-tile axis is the
innermost grid dimension; the output block ``[F_blk * B_pad, columns]`` is
revisited across row tiles and accumulated in place (initialized at
r == 0), the standard Pallas reduction pattern.  Levels wider than
``_MAX_COLUMNS`` stacked columns take several node chunks (a grid axis)
of the same product, each chunk's columns rounded up to whole 128-lane
tiles with zero columns that the wrapper drops.

Selection is :func:`tree_hist_impl`, the one place that knows the rule and
the one reader of ``SNTC_TREE_HIST``; the grower, ``ChiSqSelector`` and
``stat.ChiSquareTest`` ask it once a fit.  Off a TPU the kernel runs
through the Pallas interpreter (:func:`level_histogram_pallas` decides
that itself), which backs the CPU tests.
"""

from __future__ import annotations

import functools
import os

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
_MAX_COLUMNS = 1536  # stacked columns (3 terms x nodes x S_pad) a step
_ACC_BUDGET = 4 * 1024 * 1024  # the accumulator block [F_blk * B_pad, cols]
# sized for the v5e's 128 MiB of VMEM a core (the chip this kernel is
# measured on): half of it as the scoped limit, and a row tile's values
# under that.  A chip with less has to lower both or the call will not
# compile there.
_TILE_BUDGET = 36 * 1024 * 1024  # one-hot + A_t values of one row tile
_VMEM_LIMIT = 64 * 1024 * 1024
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


def _hist_kernel(
    binned_ref, node_ref, weight_ref, stats_ref, acc_ref,
    *, b_pad, node_chunk, cols, f_block,
):
    c = pl.program_id(1)
    r = pl.program_id(2)
    nodes = node_ref[...] - c * node_chunk  # [1, TILE] chunk-local ids
    x = weight_ref[...] * stats_ref[...]  # [S_pad, TILE] f32
    in_node = [nodes == k for k in range(node_chunk)]
    # the fold is a select, so it commutes with the split: split the
    # S_pad rows once, select them into every node's rows
    terms = [jnp.where(m, t, 0.0) for t in _split3(x) for m in in_node]
    spare = cols - len(terms) * x.shape[0]  # zero columns up to the block
    if spare:
        terms.append(jnp.zeros((spare, x.shape[1]), jnp.float32))
    a_t = jnp.concatenate(terms, axis=0).astype(jnp.bfloat16)  # exact
    bin_ids = jax.lax.broadcasted_iota(
        jnp.int32, (b_pad, x.shape[1]), 0
    )
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


def _plan(f: int, n: int, s_pad: int, n_nodes: int, b_pad: int):
    """Blocks from the static shapes alone: nodes a step (so the stacked
    columns stay under ``_MAX_COLUMNS``) and the column block that holds
    them (rounded up to whole 128-lane tiles with zero columns where the
    level takes several chunks: a block that is not the whole array has
    to be lane-aligned), features a step (all of them where the
    accumulator block fits ``_ACC_BUDGET``, so ``A_t`` is built once a
    row tile; else a multiple of 8), and the row tile: the largest power
    of two from ``_MIN_TILE`` to ``_MAX_TILE`` whose one-hot and ``A_t``
    (10 bytes an element with their float32 intermediates) fit
    ``_TILE_BUDGET``, or the largest smaller one that divides ``n`` if
    any does (a ragged tail costs a padded copy of every operand a
    call)."""
    node_chunk = max(1, min(n_nodes, _MAX_COLUMNS // (3 * s_pad)))
    cols = 3 * node_chunk * s_pad
    if node_chunk < n_nodes:
        cols = _round_up(cols, 128)
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
    return node_chunk, cols, f_block, tile_n


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "tile_n", "interpret"),
)
def level_histogram_pallas(
    binned_t: jnp.ndarray,  # [F, N] int32 (transposed bins)
    node_idx: jnp.ndarray,  # [N] int32 (-1 = dead row)
    stats_t: jnp.ndarray,  # [S, N] f32 row statistics, rows along lanes
    weight: jnp.ndarray,  # [N] f32 the tree's row weights
    *,
    n_nodes: int,
    n_bins: int,
    tile_n: int = None,
    interpret: bool = None,
) -> jnp.ndarray:
    """One tree's level histogram ``[F, n_nodes * n_bins, S]`` of
    ``weight * stats_t`` (LOCAL rows — caller psums across shards).

    Grid is ``(F / F_blk, node chunks, N / tile)``, all three from the
    static shapes (``_plan``).  ``S`` a multiple of 8 (the grower's
    ``_lane_dense_stats``) and ``N`` a multiple of the tile are taken as
    they lie; anything else is zero-padded here first.  ``interpret``
    left ``None`` means the Pallas interpreter unless the backend is a
    TPU, so no caller has to probe; tests pass it to pin either.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    f, n = binned_t.shape
    s = stats_t.shape[0]
    s_pad = _round_up(s, 8)
    b_pad = _round_up(n_bins, 16)  # the bfloat16 sublane tile
    node_chunk, cols, f_block, tile_plan = _plan(
        f, n, s_pad, n_nodes, b_pad
    )
    if tile_n is None:
        tile_n = tile_plan
    n_chunks = -(-n_nodes // node_chunk)
    n_pad = _round_up(n, tile_n)
    f_pad = _round_up(f, f_block)

    if n_pad != n:
        binned_t = jnp.pad(binned_t, ((0, 0), (0, n_pad - n)))
        node_idx = jnp.pad(
            node_idx, (0, n_pad - n), constant_values=-1
        )
        weight = jnp.pad(weight, (0, n_pad - n))
    if f_pad != f:
        binned_t = jnp.pad(binned_t, ((0, f_pad - f), (0, 0)))
    if (s_pad, n_pad) != (s, n):
        stats_t = jnp.pad(stats_t, ((0, s_pad - s), (0, n_pad - n)))

    out = pl.pallas_call(
        functools.partial(
            _hist_kernel, b_pad=b_pad, node_chunk=node_chunk, cols=cols,
            f_block=f_block,
        ),
        grid=(f_pad // f_block, n_chunks, n_pad // tile_n),
        in_specs=[
            pl.BlockSpec((f_block, tile_n), lambda i, c, r: (i, r)),
            pl.BlockSpec((1, tile_n), lambda i, c, r: (0, r)),  # node_idx
            pl.BlockSpec((1, tile_n), lambda i, c, r: (0, r)),  # weight
            pl.BlockSpec((s_pad, tile_n), lambda i, c, r: (0, r)),
        ],
        out_specs=pl.BlockSpec(
            (f_block * b_pad, cols), lambda i, c, r: (i, c)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (f_pad * b_pad, n_chunks * cols), jnp.float32
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(binned_t, node_idx[None, :], weight[None, :], stats_t)

    # columns are (chunk, term, node, stat) and a chunk's spare zeros: add
    # the three terms' partial histograms in float32, then [F, node, bin,
    # S] (the grower's layout)
    out = out.reshape(f_pad, b_pad, n_chunks, cols)
    out = out[..., :3 * node_chunk * s_pad]
    out = out.reshape(f_pad, b_pad, n_chunks, 3, node_chunk, s_pad)
    out = out.sum(axis=3).transpose(0, 2, 3, 1, 4)
    out = out.reshape(f_pad, n_chunks * node_chunk, b_pad, s_pad)
    return out[:f, :n_nodes, :n_bins, :s].reshape(f, n_nodes * n_bins, s)


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

    return (
        functools.partial(
            level_histogram_pallas, n_nodes=n_nodes, n_bins=n_bins
        ),
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
