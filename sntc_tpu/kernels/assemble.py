"""Pallas kernel: bucketed pad + mask + assemble for the serve path (r21).

``BatchPredictor`` rounds every batch up to a shape bucket before
dispatch (``serve/transform.py``): the frame's columns are padded to
the bucket by repeating the last row (``Frame.pad_rows``) and a
``VALID_COL`` mask marking the real rows is threaded through the
transform.  This module gives that step a kernel twin:
:func:`pad_assemble` pads each float32 column with a one-hot
gather-matmul — ``out[r] = a[min(r, N-1)]`` expressed as
``onehot(min(row, N-1)) @ a`` under the fp32 contract precision (one
nonzero term per output element; the default precision would feed the
MXU bf16-rounded values) — and assembles the bucketed frame with the
validity mask attached.  The pin against the numpy repeat-last-row twin
is bitwise in the interpreter and checked on the chip by
``chip_smoke.py``.

Everything that is not float32 (f64, ints, bools, strings — Mosaic
carries no f64) and anything the ``pad_fits_pallas`` guard rejects
takes the numpy twin column-by-column;
a compile failure poisons exactly this kernel's (shape, dtype, bucket)
signature through the shared ladder and the batch is served on the
twin.  Registered as ``pad_assemble`` in ``sntc_tpu.kernels.registry``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from sntc_tpu.kernels.registry import (
    KernelSpec,
    register_kernel,
    serve_kernel_call,
)

_ROW_BLOCK = 128
_LANE = 128
_VMEM_BUDGET = 4 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pad_fits_pallas(n_rows: int, n_cols: int, itemsize: int = 4) -> bool:
    """True when one output row-block's working set — the gather
    one-hot against the whole (padded) input plus the input and output
    blocks — fits the VMEM budget.  Serve buckets are small (the
    predictor's bucket ladder tops out well under a million rows ×
    a few hundred columns); anything wider pads on the host."""
    np_in = _round_up(max(n_rows, _LANE), _LANE)
    cp = _round_up(max(n_cols, _LANE), _LANE)
    work = _ROW_BLOCK * np_in + np_in * cp + _ROW_BLOCK * cp
    return work * itemsize <= _VMEM_BUDGET


def _pad_kernel(x_ref, o_ref, *, bb, n_in, np_in):
    r = pl.program_id(0)
    rows = r * bb + jax.lax.broadcasted_iota(jnp.int32, (bb, np_in), 0)
    src = jnp.minimum(rows, n_in - 1)  # repeat-last-row semantics
    cols = jax.lax.broadcasted_iota(jnp.int32, (bb, np_in), 1)
    onehot = (cols == src).astype(x_ref.dtype)
    o_ref[...] = jnp.dot(
        onehot, x_ref[...], preferred_element_type=x_ref.dtype,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("target", "interpret"))
def pad_rows_pallas(
    a: jnp.ndarray, *, target: int, interpret: bool = False
) -> jnp.ndarray:
    """Pad one ``[N, C]`` column block to ``[target, C]`` by repeating
    the last row (the :meth:`Frame.pad_rows` contract, bit-exact)."""
    n, c = a.shape
    np_in = _round_up(max(n, _LANE), _LANE)
    cp = _round_up(max(c, _LANE), _LANE)
    tp = _round_up(max(target, _ROW_BLOCK), _ROW_BLOCK)
    if np_in != n or cp != c:
        a = jnp.pad(a, ((0, np_in - n), (0, cp - c)))
    out = pl.pallas_call(
        functools.partial(
            _pad_kernel, bb=_ROW_BLOCK, n_in=n, np_in=np_in
        ),
        grid=(tp // _ROW_BLOCK,),
        in_specs=[pl.BlockSpec((np_in, cp), lambda r: (0, 0))],
        out_specs=pl.BlockSpec((_ROW_BLOCK, cp), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((tp, cp), a.dtype),
        interpret=interpret,
    )(a)
    return out[:target, :c]


def _pad_column_np(a: np.ndarray, target: int) -> np.ndarray:
    """The numpy twin — exactly ``Frame.pad_rows`` on one column."""
    pad = target - a.shape[0]
    tail = np.broadcast_to(a[-1:], (pad,) + a.shape[1:])
    return np.concatenate([a, tail])


def pad_assemble(frame, target: int, valid: np.ndarray):
    """Bucket-pad ``frame`` to ``target`` rows and attach the
    ``VALID_COL`` mask — the kernel-tier twin of
    ``frame.pad_rows(target).with_column(VALID_COL, valid)``.

    Float32 columns route through :func:`pad_rows_pallas` behind the
    shared registry ladder (guard reject / kernels-off / poisoned →
    numpy twin, counted); everything else pads on the host."""
    from sntc_tpu.core.frame import Frame
    from sntc_tpu.serve.transform import VALID_COL

    n = frame.num_rows
    cols = {}
    for name in frame.columns:
        a = frame[name]
        # the gather is a matmul: one Inf/NaN in a column would turn
        # every padded row of it into NaN (0 * inf), so such columns
        # pad on the host
        if (
            a.dtype == np.float32 and a.ndim in (1, 2) and n > 0
            and np.isfinite(a).all()
        ):
            a2 = a if a.ndim == 2 else a[:, None]
            padded = serve_kernel_call(
                "pad_assemble",
                (a2,),
                lambda impl, a2=a2: np.asarray(
                    pad_rows_pallas(
                        jnp.asarray(a2), target=target,
                        interpret=(impl == "interpret"),
                    )
                ),
                lambda a=a: _pad_column_np(a, target),
                static=(target,),
                guard_kwargs={
                    "n_rows": n,
                    "n_cols": a2.shape[1],
                    "itemsize": a2.dtype.itemsize,
                },
            )
            if padded.ndim != a.ndim:  # kernel path returns [target, 1]
                padded = padded[:, 0]
            cols[name] = padded
        else:
            cols[name] = _pad_column_np(a, target)
    cols[VALID_COL] = np.asarray(valid, dtype=bool)
    return Frame._wrap(cols, int(target))


def _smoke_case(rows: int):
    """A ragged 78-feature micro-batch padded up to its bucket."""
    rng = np.random.default_rng(0)
    n = rows - rows // 4
    return (
        functools.partial(pad_rows_pallas, target=rows),
        lambda a: _pad_column_np(np.asarray(a), rows),
        (rng.normal(size=(n, 78)).astype(np.float32),),
        0.0,
    )


register_kernel(
    KernelSpec(
        name="pad_assemble",
        module="sntc_tpu/kernels/assemble.py",
        guard_name="pad_fits_pallas",
        guard=pad_fits_pallas,
        tolerance="bitwise (one-hot gather, fp32 contract), float32 only",
        fallback="numpy Frame.pad_rows twin, column-by-column",
        smoke_case=_smoke_case,
    )
)
