"""The feature stages' one whole-column host copy: rows into a matrix.

The assembler's feature-major matrix and the selectors' take of it are the
same work: N-long rows copied into one fresh ``[len(rows), N]`` allocation.
One thread does that at the speed of its page faults (about 1 GB/s on the
chip hosts), and numpy holds no GIL in a copy or in ``isfinite`` over
non-object data, so a fit-scale copy is shared out among a pool of
workers, a row at a time.  A serving micro-batch is under the size where
a pool pays and takes the single library call.  Imports nothing from jax.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# bytes of the result at and over which a pool copies faster than one call,
# and the worker count past which it stops gaining: PERF.md §6, PR 38
POOL_MIN_BYTES = 32 << 20
POOL_MAX_WORKERS = 8


def pool_workers(n_rows: int, nbytes: int, *,
                 min_bytes: int = POOL_MIN_BYTES) -> int:
    """Workers :func:`stack_rows` gives a copy of ``nbytes`` into
    ``n_rows`` rows: 1 (the single call) under ``min_bytes``."""
    if nbytes < min_bytes:
        return 1
    return min(len(os.sched_getaffinity(0)), n_rows, POOL_MAX_WORKERS)


def stack_rows(rows, dtype, *, finite: bool = False, workers: int = 1):
    """``(np.array(rows, dtype), flags)`` for 1-D numeric ``rows`` of one
    length: the C-contiguous ``[len(rows), N]`` matrix, numpy's own cast,
    and with ``finite`` one boolean a row, whether all its values (after
    the cast) are finite, taken by the worker that copied the row while
    it is warm (``None`` when not asked).  ``workers`` is
    :func:`pool_workers`' answer for the result's size; the pool is made
    and joined inside the call."""
    if workers <= 1:
        out = np.array(rows, dtype=dtype)
        return out, (np.isfinite(out).all(axis=1) if finite else None)
    out = np.empty((len(rows), len(rows[0])), dtype)
    ok = np.ones(len(rows), bool) if finite else None

    def share(w: int) -> None:
        # one task a worker, its rows strided: a task a row costs more
        # GIL hand-overs than the copy of a small row takes
        for j in range(w, len(rows), workers):
            out[j] = rows[j]
            if finite:
                ok[j] = np.isfinite(out[j]).all()

    with ThreadPoolExecutor(max_workers=workers) as pool:
        list(pool.map(share, range(workers)))
    return out, ok
