"""Pallas TPU kernel: per-level tree histogram as MXU one-hot matmuls.

The tree grower's hot op (SURVEY.md §3.2/§7.2 item 1) is the
(node, feature, bin, stat) sufficient-statistics accumulation.  The XLA
fallback (sntc_tpu/ops/histogram.py + grower) lowers it to scatter-adds,
which serialize on TPU.  This kernel recasts it as dense matmuls:

    for each (feature f, row-block r):
        ids     = node_idx * B + bin[f]                  # [TILE_N]
        onehot  = (iota_cols == ids)                     # [TILE_N, NBpad]
        acc[f] += stats_blockᵀ @ onehot                  # [S, NBpad] on MXU

so the accumulation rides the systolic array instead of scatter units.
The row-block axis is the innermost grid dimension; the output block for
feature ``f`` is revisited across row-blocks and accumulated in place
(initialized at r == 0) — the standard Pallas reduction pattern.

Layouts: ``binned`` arrives transposed ``[F, N]`` so each (f, r) block is
lane-contiguous; the output is ``[F, S_pad, NB_pad]`` with the large
node×bin axis last (128-lane aligned).  Stats arrive pre-weighted
(bagging × user weight × active mask), so padded/dead rows contribute 0.

Selection (``_resolve_tree_hist``): on a TPU backend ``grower`` and
``ChiSqSelector`` take this kernel by default whenever a mesh is given and
the level fits the VMEM budget; elsewhere the XLA segment-sum.
``SNTC_TREE_HIST`` overrides; interpret mode backs the CPU tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


_F_BLOCK = 8  # features per grid step (TPU sublane granularity)
_ONEHOT_BUDGET = 4 * 1024 * 1024  # VMEM budget for the in-kernel one-hot
_MIN_TILE = 128


def hist_fits_pallas(n_nodes: int, n_bins: int) -> bool:
    """True if a level histogram of this width fits the kernel's VMEM
    budget at the minimum row tile (beyond it, the one-hot block alone
    would exhaust VMEM — callers fall back to the segment_sum impl)."""
    nb_pad = _round_up(max(n_nodes * n_bins + 1, 128), 128)
    return _MIN_TILE * nb_pad * 4 <= _ONEHOT_BUDGET


def _resolve_tree_hist(n_nodes_max: int, n_bins: int, mesh=None) -> str:
    """The historical ``SNTC_TREE_HIST`` selection semantics, verbatim
    (r21 moved the dispatch behind the kernel registry; this resolver
    keeps the fit-side behavior byte-identical)."""
    import os

    import jax

    on_tpu = jax.default_backend() == "tpu"
    impl = os.environ.get(
        "SNTC_TREE_HIST", "pallas" if on_tpu else "segment"
    )
    if impl == "pallas" and (
        mesh is None or not hist_fits_pallas(n_nodes_max, n_bins)
    ):
        return "segment"
    return impl


def resolve_hist_impl(n_nodes_max: int, n_bins: int, mesh=None) -> str:
    """Histogram impl selection shared by the tree grower and
    ChiSqSelector: the one-hot MXU kernel on TPU (scatter-adds serialize
    there; its speed on the local v5e is not measured), segment_sum
    elsewhere, when no mesh is available, or when the widest level
    overflows the kernel's VMEM budget.  ``SNTC_TREE_HIST`` overrides.

    Since r21 the call routes through the shared kernel registry
    (``sntc_tpu.kernels.registry``) so the fit-side kernel shares the
    serve tier's fit-guard/fallback/cost accounting; the selection
    itself is unchanged (``_resolve_tree_hist``)."""
    from sntc_tpu.kernels.registry import resolve_impl

    return resolve_impl(
        "tree_hist", n_nodes_max=n_nodes_max, n_bins=n_bins, mesh=mesh
    )


def _hist_kernel(
    binned_ref, node_ref, stats_ref, acc_ref, *, n_bins, nb_pad, f_block
):
    r = pl.program_id(1)
    nodes = node_ref[0, :]  # [TILE_N] int32 (-1 = inactive)
    stats_t = stats_ref[:].T  # [S_pad, TILE_N]
    alive = nodes >= 0
    base = nodes * n_bins
    for j in range(f_block):  # unrolled: f_block matmuls per grid step
        bins = binned_ref[j, :]  # [TILE_N] int32 (feature f+j's bins)
        ids = jnp.where(alive, base + bins, nb_pad - 1)
        # dead rows point at the last padded column, which is sliced off;
        # their stats are also zero (pre-masked), so this is belt & braces
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, (bins.shape[0], nb_pad), 1)
            == ids[:, None]
        ).astype(jnp.float32)
        # fp32 contract: at the default precision the MXU takes the
        # stats bf16-rounded (measured 3.9e-3 max rel error vs the
        # segment_sum twin on a v5e; 2.4e-7 under HIGHEST — PERF.md)
        contrib = jnp.dot(
            stats_t, onehot, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )  # [S_pad, NB_pad]

        @pl.when(r == 0)
        def _init(j=j, contrib=contrib):
            acc_ref[j] = contrib

        @pl.when(r != 0)
        def _acc(j=j, contrib=contrib):
            acc_ref[j] += contrib


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "tile_n", "interpret"),
)
def level_histogram_pallas(
    binned_t: jnp.ndarray,  # [F, N] int32 (transposed bins)
    node_idx: jnp.ndarray,  # [N] int32
    weighted_stats: jnp.ndarray,  # [N, S] f32, pre-weighted/masked
    *,
    n_nodes: int,
    n_bins: int,
    tile_n: int = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """One tree's level histogram ``[n_nodes * n_bins, S]`` (LOCAL rows —
    caller psums across shards).

    Grid is ``(F/8, N/tile)``: feature blocks of 8 satisfy the TPU sublane
    tiling rule (a block's second-to-last dim must be a multiple of 8), and
    the row tile adapts so the in-VMEM one-hot ``[tile, NB_pad]`` stays
    ~4 MB regardless of the node×bin width (GBT's 128-bin levels would
    otherwise blow VMEM).
    """
    f, n = binned_t.shape
    s = weighted_stats.shape[1]
    nb = n_nodes * n_bins
    nb_pad = _round_up(max(nb + 1, 128), 128)  # +1: dead-row dump column
    s_pad = _round_up(s, 8)
    if tile_n is None:
        budget = _ONEHOT_BUDGET // (nb_pad * 4)
        tile_n = max(_MIN_TILE, min(2048, (budget // 128) * 128))
    n_pad = _round_up(n, tile_n)
    f_pad = _round_up(f, _F_BLOCK)

    if n_pad != n:
        binned_t = jnp.pad(binned_t, ((0, 0), (0, n_pad - n)))
        node_idx = jnp.pad(
            node_idx, (0, n_pad - n), constant_values=-1
        )
        weighted_stats = jnp.pad(
            weighted_stats, ((0, n_pad - n), (0, 0))
        )
    if f_pad != f:
        binned_t = jnp.pad(binned_t, ((0, f_pad - f), (0, 0)))
    if s_pad != s:
        weighted_stats = jnp.pad(weighted_stats, ((0, 0), (0, s_pad - s)))

    node_2d = node_idx[None, :]  # [1, N]
    grid = (f_pad // _F_BLOCK, n_pad // tile_n)

    out = pl.pallas_call(
        functools.partial(
            _hist_kernel, n_bins=n_bins, nb_pad=nb_pad, f_block=_F_BLOCK
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_F_BLOCK, tile_n), lambda i, r: (i, r)),  # binned_t
            pl.BlockSpec((1, tile_n), lambda i, r: (0, r)),  # node_idx
            pl.BlockSpec((tile_n, s_pad), lambda i, r: (r, 0)),  # stats
        ],
        out_specs=pl.BlockSpec(
            (_F_BLOCK, s_pad, nb_pad), lambda i, r: (i, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((f_pad, s_pad, nb_pad), jnp.float32),
        interpret=interpret,
    )(binned_t, node_2d, weighted_stats)

    # [F_pad, S_pad, NB_pad] -> [F, NB, S] (the grower's layout)
    return out[:f, :s, :nb].transpose(0, 2, 1)


# registered behind the shared kernel capability registry (r21):
# selection stays the historical SNTC_TREE_HIST resolver above, but the
# fit-side kernel now shares the serve tier's registry ⇔ docs ⇔ tests
# drift check and the sntc_kernel_* accounting
from sntc_tpu.kernels.registry import KernelSpec, register_kernel  # noqa: E402

def _smoke_case(rows: int):
    """One depth-4 level of the bench config 3 forest: 16 nodes x 32
    bins over the 40 chi-square-selected features, 15 class stats."""
    import numpy as np

    n_nodes, n_bins, f, s = 16, 32, 40, 15
    rng = np.random.default_rng(0)
    node_idx = rng.integers(-1, n_nodes, size=rows).astype(np.int32)
    stats = rng.random((rows, s)).astype(np.float32)
    stats[node_idx < 0] = 0.0  # pre-masked, as the grower guarantees

    def twin(binned_t, node_idx, stats):
        ids = jnp.maximum(node_idx, 0)[None, :] * n_bins + binned_t
        return jax.vmap(
            lambda i: jax.ops.segment_sum(
                stats, i, num_segments=n_nodes * n_bins
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
            stats,
        ),
        1e-5,
    )


register_kernel(
    KernelSpec(
        name="tree_hist",
        module="sntc_tpu/ops/pallas_histogram.py",
        guard_name="hist_fits_pallas",
        guard=hist_fits_pallas,
        tolerance="<=1e-5 rel f32 (pre-weighted stats accumulation)",
        fallback="XLA segment_sum level histogram (ops/histogram.py)",
        env="SNTC_TREE_HIST",
        resolver=_resolve_tree_hist,
        smoke_case=_smoke_case,
    )
)
