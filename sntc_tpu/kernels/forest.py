"""Pallas TPU kernel: fused RF/GBT/DT ensemble traversal (r21).

The serving node-walk (``grower.forest_leaf_stats``) is ``max_depth``
rounds of data-dependent gathers — feature id at the current node, the
row's value of that feature, the node's threshold — which XLA lowers to
serialized dynamic-slice chains per level.  This kernel keeps one
(tree, row-block) tile resident in VMEM and replaces every gather with
an iota-mask select (one nonzero term per row, so the float sum is
exact) plus a final one-hot MXU matmul for the leaf-stat gather:

    for each (tree t, row-block r):
        node = 0
        repeat max_depth:
            f, thr   = select(node == iota_M, feature/threshold row)
            xv       = select(f == iota_F, X block)
            node     = 2*node + 1 + (xv >= thr)   where internal
        out[t, r] = onehot(node) @ leaf_stats[t]   # MXU, fp32 contract

Trees ride the grid, so the whole forest traverses in one launch with
no per-level host round-trips.  Mosaic layout rules shape the code: the
per-row state (``node``, the selected feature id / threshold / value)
stays a ``[BN, 1]`` column so every compare is a lane broadcast, never
a lane→sublane relayout; ``feature``/``threshold`` arrive as
``[T, 1, Mp]`` so a one-tree block's trailing dims equal the array's
(the (8, 128) block rule rejects a ``(1, Mp)`` block over ``[T, Mp]``
for any T > 1); feature ids ride as float32 (ids < 2**24 are exact) so
the lane reductions are all float.  The final matmul asks for the fp32
contract precision — the default would feed the MXU bf16-rounded leaf
stats.  Compiled (Mosaic) the kernel is float32 only; the documented
tolerance vs the lowered-jnp twin (``forest_leaf_stats`` itself) is
<=1e-5 rel, checked on the chip by ``chip_smoke.py``.

Registered as ``forest_traversal`` in ``sntc_tpu.kernels.registry``;
``forest_fits_pallas`` guards dtype and the VMEM working set, interpret
mode backs the CPU tier-1 matrix, and a compile failure poisons exactly
this kernel's signature back onto the XLA node-walk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from sntc_tpu.kernels.registry import KernelSpec, register_kernel

_ROW_BLOCK = 128  # rows per grid step (f32 lane tile)
_LANE = 128
_VMEM_BUDGET = 4 * 1024 * 1024  # in-kernel working set budget (bytes)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def forest_fits_pallas(
    n_nodes: int, n_features: int, n_stats: int, itemsize: int = 4
) -> bool:
    """True when the inputs are float32 (the only dtype the compiled
    kernel carries) and one (tree, row-block) tile's working set — the
    node one-hot, the feature-select mask, and the padded leaf-stat
    block — fits the kernel's VMEM budget.  Beyond it (freak
    depth/width forests, x64 features) callers stay on the XLA
    node-walk."""
    if itemsize != 4:
        return False
    mp = _round_up(max(n_nodes, _LANE), _LANE)
    fp = _round_up(max(n_features, _LANE), _LANE)
    sp = _round_up(max(n_stats, _LANE), _LANE)
    work = _ROW_BLOCK * mp + _ROW_BLOCK * fp + mp * sp
    return work * itemsize <= _VMEM_BUDGET


def _forest_kernel(
    x_ref, feat_ref, thr_ref, leaf_ref, out_ref, *, max_depth, bn, mp, fp
):
    x = x_ref[...]  # [BN, Fp]
    feat = feat_ref[0]  # [1, Mp] float ids (-1 leaf, -2 absent)
    thr = thr_ref[0]  # [1, Mp]
    leaf = leaf_ref[0]  # [Mp, Sp]
    node = jnp.zeros((bn, 1), jnp.int32)
    cols_m = jax.lax.broadcasted_iota(jnp.int32, (bn, mp), 1)
    cols_f = jax.lax.broadcasted_iota(jnp.int32, (bn, fp), 1)
    zero_f = jnp.zeros((), feat.dtype)
    zero_t = jnp.zeros((), thr.dtype)
    zero_x = jnp.zeros((), x.dtype)
    for _ in range(max_depth):
        at_node = cols_m == node  # [BN, Mp] one column per row
        f = jnp.sum(jnp.where(at_node, feat, zero_f), axis=1, keepdims=True)
        t = jnp.sum(jnp.where(at_node, thr, zero_t), axis=1, keepdims=True)
        is_internal = f >= 0
        fc = jnp.where(is_internal, f, zero_f).astype(jnp.int32)
        xv = jnp.sum(
            jnp.where(cols_f == fc, x, zero_x), axis=1, keepdims=True
        )
        go_right = (xv >= t).astype(jnp.int32)
        node = jnp.where(is_internal, 2 * node + 1 + go_right, node)
    onehot = (cols_m == node).astype(leaf.dtype)
    out_ref[0] = jnp.dot(
        onehot, leaf, preferred_element_type=leaf.dtype,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(
    jax.jit, static_argnames=("max_depth", "interpret")
)
def forest_leaf_stats_pallas(
    X: jnp.ndarray,  # [N, F]
    feature: jnp.ndarray,  # [T, M] int32
    threshold: jnp.ndarray,  # [T, M]
    leaf_stats: jnp.ndarray,  # [T, M, S]
    *,
    max_depth: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Kernel twin of :func:`sntc_tpu.models.tree.grower.forest_leaf_stats`
    — leaf stats ``[T, N, S]`` for every (tree, row)."""
    n, f = X.shape
    t, m = feature.shape
    s = leaf_stats.shape[2]
    np_ = _round_up(max(n, _ROW_BLOCK), _ROW_BLOCK)
    fp = _round_up(max(f, _LANE), _LANE)
    mp = _round_up(max(m, _LANE), _LANE)
    sp = _round_up(max(s, _LANE), _LANE)
    if np_ != n or fp != f:
        X = jnp.pad(X, ((0, np_ - n), (0, fp - f)))
    # padded nodes are unreachable (the walk never leaves [0, M));
    # -2 marks them absent exactly like the grower's layout
    feature = jnp.pad(
        feature.astype(jnp.float32), ((0, 0), (0, mp - m)),
        constant_values=-2,
    )[:, None, :]
    threshold = jnp.pad(threshold, ((0, 0), (0, mp - m)))[:, None, :]
    leaf_stats = jnp.pad(leaf_stats, ((0, 0), (0, mp - m), (0, sp - s)))

    grid = (t, np_ // _ROW_BLOCK)
    out = pl.pallas_call(
        functools.partial(
            _forest_kernel,
            max_depth=max_depth, bn=_ROW_BLOCK, mp=mp, fp=fp,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_ROW_BLOCK, fp), lambda ti, r: (r, 0)),  # X
            pl.BlockSpec((1, 1, mp), lambda ti, r: (ti, 0, 0)),  # feature
            pl.BlockSpec((1, 1, mp), lambda ti, r: (ti, 0, 0)),  # threshold
            pl.BlockSpec((1, mp, sp), lambda ti, r: (ti, 0, 0)),  # leaf
        ],
        out_specs=pl.BlockSpec(
            (1, _ROW_BLOCK, sp), lambda ti, r: (ti, r, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((t, np_, sp), leaf_stats.dtype),
        interpret=interpret,
    )(X, feature, threshold, leaf_stats)
    return out[:, :n, :s]


def traverse_forest(
    X, feature, threshold, leaf_stats, *, max_depth: int,
    traversal: str = "xla",
):
    """Traversal dispatch inside the jitted serve programs: the
    ``traversal`` token is a static argument resolved by the registry
    ladder at the ``_predict_all_dev`` boundary (``"xla"`` is the
    lowered-jnp twin the kernel is pinned against)."""
    if traversal in ("pallas", "interpret"):
        return forest_leaf_stats_pallas(
            X, feature, threshold, leaf_stats,
            max_depth=max_depth, interpret=(traversal == "interpret"),
        )
    from sntc_tpu.models.tree.grower import forest_leaf_stats

    return forest_leaf_stats(
        X, feature, threshold, leaf_stats, max_depth=max_depth
    )


def _smoke_case(rows: int):
    """The bench config 3 forest (20 trees x depth 5, 78 features, 15
    classes) with random splits: any feature id in [-1, F) walks a
    valid path, so a random heap exercises every level."""
    import numpy as np

    from sntc_tpu.models.tree.grower import forest_leaf_stats

    rng = np.random.default_rng(0)
    t, depth, f, s = 20, 5, 78, 15
    m = 2 ** (depth + 1) - 1
    args = (
        rng.normal(size=(rows, f)).astype(np.float32),
        rng.integers(-1, f, size=(t, m)).astype(np.int32),
        rng.normal(size=(t, m)).astype(np.float32),
        rng.random((t, m, s)).astype(np.float32),
    )
    return (
        functools.partial(forest_leaf_stats_pallas, max_depth=depth),
        functools.partial(forest_leaf_stats, max_depth=depth),
        args,
        1e-5,
    )


register_kernel(
    KernelSpec(
        name="forest_traversal",
        module="sntc_tpu/kernels/forest.py",
        guard_name="forest_fits_pallas",
        guard=forest_fits_pallas,
        tolerance="<=1e-5 rel, float32 only",
        fallback="XLA node-walk (grower.forest_leaf_stats)",
        smoke_case=_smoke_case,
    )
)
