"""SPMD collectives — the ``treeAggregate`` / ``TorrentBroadcast`` analog.

Spark's per-iteration comm triad (SURVEY.md §3.1, §5.8):

    broadcast(params)  ->  per-partition seqOp  ->  tree-reduce combOp to driver

collapses on TPU into one SPMD program: params are replicated by sharding,
the seqOp is the per-shard computation, and the combOp is ``jax.lax.psum``
over the ICI ``"data"`` axis — on-device, no host hop, no serialization
(netty RPC / shuffle / torrent broadcast all deleted per SURVEY.md §2.5).

Built on the r22 mesh substrate (``sntc_tpu.parallel.mesh``): the
per-shard map + named-axis reduce is expressed with
:func:`~sntc_tpu.parallel.mesh.map_reduce_at`, host↔device placement is
attributed through the :class:`~sntc_tpu.utils.profiling.TransferLedger`
plane, and every dispatch records ``sntc_collective_*`` evidence
(dispatches + ring-allreduce wire bytes per (op, axis)).

``tree_aggregate(fn, mesh, *arrays)`` is the named API estimators use; it
shards each array's leading axis over the mesh, applies ``fn`` per shard, and
``psum``s every leaf of the result.  Rows are padded to a shard multiple with
an explicit weight column so padding contributes zero (callers thread the
weight through ``fn``).

**Elastic mesh (r22):** a ``device_lost`` surfacing from a dispatch no
longer flips the whole host HOST_DEGRADED — the aggregate *resizes*: the
data axis shrinks to the largest power-of-two shard count the padded
batch still divides over, the batch is re-placed on the surviving
devices, the decision is journaled (``mesh_resize``) on the attached
:class:`~sntc_tpu.resilience.device.DeviceFaultDomain`, and the dispatch
retries on the smaller mesh.  A per-shard ``RESOURCE_EXHAUSTED`` rides
the existing ``device_oom`` ladder instead: the padded batch splits into
two shard-aligned row halves whose partials SUM to the full result
(every aggregate ``fn`` returns an additive sum-tree by contract), with
the recursion depth bounded by the domain's ``oom_split_depth``.
"""

from __future__ import annotations

import functools
import math
import os
import weakref
from collections import OrderedDict
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sntc_tpu.obs import module_of, span
from sntc_tpu.obs.metrics import inc
from sntc_tpu.parallel.mesh import (
    DATA_AXIS,
    map_reduce_at,
    payload_nbytes,
    record_collective,
    record_mesh_shape,
)
from sntc_tpu.resilience import (
    CircuitOpenError,
    RetryPolicy,
    breaker_for,
    fault_point,
    with_retries,
)
from sntc_tpu.resilience.policy import int_from_env


_MODULE = module_of(__name__)


def _dispatch_breaker():
    """Optional circuit breaker for aggregate dispatch:
    ``SNTC_COLLECTIVE_BREAKER=1`` shares one process-wide breaker for
    site ``collective.dispatch`` across every aggregate — when a
    backend is down hard, dispatch fails FAST with
    :class:`CircuitOpenError` instead of burning a retry budget per
    call.  Cooldown via ``SNTC_COLLECTIVE_BREAKER_COOLDOWN_S``
    (default 30).  Default off: dispatch behavior is unchanged."""
    if int_from_env("SNTC_COLLECTIVE_BREAKER", 0) <= 0:
        return None
    cooldown = int_from_env("SNTC_COLLECTIVE_BREAKER_COOLDOWN_S", 30)
    return breaker_for("collective.dispatch", cooldown_s=float(cooldown))


def _dispatch_policy() -> "RetryPolicy | None":
    """Optional retry for aggregate dispatch (site
    ``collective.dispatch``): ``SNTC_COLLECTIVE_RETRIES=N`` arms N
    in-place retries with deterministic backoff for dispatch failures
    that RAISE (transient backend RPC/transfer errors, injected
    faults).  It cannot help the XLA:CPU rendezvous-timeout class that
    SIGABRTs the whole process — process-level isolation
    (``bench.py --isolate``) is the mitigation there.  Default 0
    (single-shot: dispatch failures propagate unchanged)."""
    retries = int_from_env("SNTC_COLLECTIVE_RETRIES", 0, minimum=0)
    if retries <= 0:
        return None
    return RetryPolicy(
        max_attempts=retries + 1, base_delay_s=0.1, multiplier=2.0,
        max_delay_s=10.0, jitter=0.1, seed=0,
    )


# ---------------------------------------------------------------------------
# compute fault-domain attachment — the collective layer's hook into the
# PR-13 device state machine.  Fits that want mesh_resize / oom_split
# decisions journaled attach a DeviceFaultDomain process-wide (bench
# chaos legs, the serve daemon's fit path); unattached, the elastic
# responses still run and still emit events/metrics, they just have no
# journal to land in.
# ---------------------------------------------------------------------------

_COLLECTIVE_DOMAIN = None


def set_collective_domain(domain) -> None:
    """Attach (or detach with ``None``) the process-wide
    :class:`~sntc_tpu.resilience.device.DeviceFaultDomain` that
    collective-layer survival decisions journal into."""
    global _COLLECTIVE_DOMAIN
    _COLLECTIVE_DOMAIN = domain


def get_collective_domain():
    return _COLLECTIVE_DOMAIN


def _resize_enabled() -> bool:
    """``SNTC_MESH_RESIZE=0`` disables the elastic response (a lost
    device then propagates to the caller / the host domain, the pre-r22
    behavior).  Default on."""
    return int_from_env("SNTC_MESH_RESIZE", 1) > 0


def _ledger_movement(nbytes: int) -> None:
    """Attribute one substrate upload to every active
    :class:`TransferLedger` (tenant/scope-attributed like serve
    dispatches).  ``record_movement`` counts arrays + bytes but NOT a
    dispatch — the dispatch series stays "fused program calls"."""
    try:
        from sntc_tpu.utils.profiling import active_ledgers

        for led in active_ledgers():
            led.record_movement(uploads=1, upload_bytes=int(nbytes))
    except Exception:
        pass


# ---------------------------------------------------------------------------
# device-residency cache — the BlockManager / ``df.cache()`` analog.
#
# Frames are immutable by contract (sntc_tpu.core.frame), so re-sharding the
# SAME host array (re-fit on one dataset, CrossValidator's final refit, a
# second estimator reading the same column) can return the already-resident
# device copy instead of re-crossing the host↔device link (its cost on
# the local chip is not measured), and Spark survives the same
# re-scan problem only via explicit ``.cache()``.  Identity-keyed through a
# WEAK reference to the host array: a live array re-used is a hit; once the
# caller drops the array the entry dies with it (no pinning of throwaway
# uploads) and a recycled ``id`` can never false-hit because the dead
# weakref invalidates the entry.  Byte-bounded LRU on the device side, the
# bytes counted a device (:func:`_device_bytes`); ``SNTC_DEVICE_CACHE_MB=0``
# disables.
# ---------------------------------------------------------------------------

_DEVICE_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()


def _device_cache_max_bytes() -> int:
    return int(os.environ.get("SNTC_DEVICE_CACHE_MB", "2048")) * (1 << 20)


def _device_bytes(dev) -> int:
    """Bytes ``dev`` takes on the fullest device that holds a part of it:
    what the cache's budget bounds.  A row-sharded copy costs each device
    its shard, so a mesh of four keeps what a mesh of one keeps of a
    quarter of the rows."""
    return math.prod(dev.sharding.shard_shape(dev.shape)) * dev.dtype.itemsize


def _spans_processes(mesh: Mesh) -> bool:
    """True when the mesh includes devices of OTHER processes — the
    multi-host case where plain ``device_put`` cannot build the global
    array."""
    if jax.process_count() == 1:
        return False
    me = jax.process_index()
    return any(d.process_index != me for d in mesh.devices.flat)


def _global_shard_put(arr_p, sharding):
    """Multi-host construction of a row-sharded global array: every
    process holds the FULL host array (single-host data plane, same on
    all processes) and serves its addressable shards by slicing — the
    ``make_array_from_callback`` path ``device_put`` cannot take across
    processes.  A ``jax.Array`` input (a device-resident column from an
    upstream stage) is resharded globally instead: fetching it to host
    would fail when it spans non-addressable devices."""
    if isinstance(arr_p, jax.Array):
        return jax.device_put(arr_p, sharding)
    return jax.make_array_from_callback(
        arr_p.shape, sharding, lambda idx: np.asarray(arr_p[idx])
    )


def _shard_attrs(arr, sharding) -> dict:
    """``shards`` / ``shard_bytes`` of the ``h2d.*`` spans: the devices
    ``sharding`` cuts ``arr`` over (the mesh axes its spec names; 1 for a
    replicated placement or a mesh of one) and the bytes one of them
    takes."""
    shards = 1
    mesh = getattr(sharding, "mesh", None)
    for entry in getattr(sharding, "spec", ()):
        for axis in (entry,) if isinstance(entry, str) else (entry or ()):
            shards *= int(mesh.shape[axis])
    nbytes = int(getattr(arr, "nbytes", 0))
    return {"shards": shards, "shard_bytes": -(-nbytes // shards)}


def _put_sharded(arr, sharding):
    """The one routing point: global construction when the mesh spans
    processes, plain ``device_put`` otherwise.  Every byte that crosses
    here lands in the active transfer ledgers — the r22 fix for
    collective dispatches undercounting the ``sntc_transfer_*``
    series."""
    nbytes = getattr(arr, "nbytes", 0)
    with span("h2d.put", bytes=int(nbytes), module=_MODULE,
              **_shard_attrs(arr, sharding)):
        if _spans_processes(sharding.mesh):
            out = _global_shard_put(arr, sharding)
        else:
            out = jax.device_put(arr, sharding)
    _ledger_movement(nbytes)
    return out


@functools.partial(jax.jit, static_argnames=("rows",))
def _pad_shard_rows(row0, real=None, *, rows: int):
    """One shard's ``rows`` rows, made on the shard's own chip: row 0 of
    the whole array replicated, with the real rows the shard was sent
    (none for a shard past the array's end) written over its head.
    Module-level on purpose: a fresh ``jit`` object a call would miss the
    compile cache every fit (see ``gbt._broadcast_classes``).  An
    update-slice and not a ``concatenate``: the TPU compiler turns a
    concatenate into a ``maximum`` of two padded operands, which flushes
    denormals and rewrites NaN payloads (read on the chip, PERF.md §6
    PR 36); this form only moves bytes, so the shard is bit for bit what
    ``device_put`` of a host-padded copy gives."""
    out = jnp.broadcast_to(row0, (rows,) + row0.shape[1:])
    if real is None:
        return out
    return jax.lax.dynamic_update_slice(out, real, (0,) * real.ndim)


def _put_row_shards(arr: np.ndarray, n_pad: int, sharding):
    """Place a host array row-sharded and padded to ``n_pad`` rows without
    copying it on the host first: every device is sent a VIEW of its real
    rows (strided or not: the runtime reads a feature-major ``base.T`` in
    place, as it reads ``device_put``'s own shard views), and a shard
    short of its rows is padded where it lands, by
    :func:`_pad_shard_rows` and a one-row put of row 0.  The result is the
    array ``device_put(np.concatenate([arr, row 0 ...]), sharding)`` gives:
    same shape, dtype, sharding and values.  A short shard's unpadded
    buffer lives on its chip beside the padded one for the length of that
    one program: nothing here keeps it past the call."""
    n = arr.shape[0]
    shape = (n_pad,) + arr.shape[1:]
    rows = sharding.shard_shape(shape)[0]
    index_map = sharding.addressable_devices_indices_map(shape)
    attrs = _shard_attrs(arr, sharding)
    # Each shard's copy is waited for.  Between shards: four 1.3 GB copies
    # issued together leave the last 4.7-5.0 s behind the others' 0.18 s;
    # one after another they take 0.14 s each.  After the last: the host
    # would otherwise run ahead and enqueue the fit's next programs, whose
    # buffers are allocated at enqueue, while the short shard's unpadded
    # rows still wait for their pad program: +0.55 GB at a boosted fit's
    # peak (four and one v5e chips: PERF.md §7 (vi), (xv)).  Nothing is
    # copied on the host meanwhile, and ``h2d.put`` times the copy.
    with span("h2d.put", bytes=int(arr.nbytes), module=_MODULE, **attrs):
        parts = []  # a device's real rows; None for a shard past the end
        for dev, idx in index_map.items():
            start = idx[0].indices(n_pad)[0]
            if start >= n:
                parts.append(None)
                continue
            parts.append(jax.device_put(arr[start:start + rows], dev))
            parts[-1].block_until_ready()  # numpy clipped the slice at n
    short = [
        (i, dev) for i, dev in enumerate(index_map)
        if parts[i] is None or parts[i].shape[0] < rows
    ]
    row_bytes = arr.nbytes // n
    padded_bytes = len(short) * rows * row_bytes
    with span("h2d.pad", bytes=padded_bytes, where="device",
              module=_MODULE, **attrs):
        for i, dev in short:
            row0 = jax.device_put(arr[:1], dev)
            parts[i] = _pad_shard_rows(row0, parts[i], rows=rows)
    inc("sntc_transfer_pad_bytes_total", padded_bytes, where="device")
    # what crossed: the array once, and row 0 again for every padded shard
    _ledger_movement(int(arr.nbytes) + len(short) * row_bytes)
    return jax.make_array_from_single_device_arrays(shape, sharding, parts)


def _cached_shard_put(arr, n_pad: int, sharding):
    """Pad ``arr`` to ``n_pad`` rows (replicating row 0) and place it under
    ``sharding``, memoized on the identity of the UNPADDED array.

    Where the pad happens: a fit-scale host array (1 MiB and over) is
    never copied on the host; its shards are put from views and the short
    one is padded on its chip (:func:`_put_row_shards`, ``h2d.pad``
    ``where="device"``).  A smaller host array, and any host array on a
    mesh that spans processes, is padded by a host ``np.concatenate``
    (``where="host"``: microseconds for kilobytes, where a device pad
    costs a dispatch and a compile a distinct shape).  A ``jax.Array`` is
    padded on the device it lives on."""
    fit_scale = isinstance(arr, np.ndarray) and arr.nbytes >= (1 << 20)
    cacheable = fit_scale and _device_cache_max_bytes() > 0
    # sweep entries whose host array was garbage-collected
    for k in [k for k, e in _DEVICE_CACHE.items() if e[0]() is None]:
        del _DEVICE_CACHE[k]
    key = (id(arr), n_pad, sharding)
    if cacheable:
        hit = _DEVICE_CACHE.get(key)
        if hit is not None and hit[0]() is arr:
            _DEVICE_CACHE.move_to_end(key)
            return hit[1]
    n = arr.shape[0]
    if cacheable:
        # make room BEFORE the new copy lands, not after: the placement
        # below holds a short shard twice for the length of one program,
        # and an entry the budget is about to evict must not stand beside
        # that (same survivors as evicting after the insert)
        total = math.prod(
            sharding.shard_shape((n_pad,) + arr.shape[1:])
        ) * jax.dtypes.canonicalize_dtype(arr.dtype).itemsize
        total += sum(_device_bytes(e[1]) for e in _DEVICE_CACHE.values())
        while total > _device_cache_max_bytes() and _DEVICE_CACHE:
            total -= _device_bytes(_DEVICE_CACHE.popitem(last=False)[1][1])
    if n_pad == n:
        dev = _put_sharded(arr, sharding)
    elif isinstance(arr, jax.Array):
        # device-resident input: pad on device, never revisit the host
        pad_block = jnp.broadcast_to(arr[:1], (n_pad - n,) + arr.shape[1:])
        dev = _put_sharded(
            jnp.concatenate([arr, pad_block], axis=0), sharding
        )
    elif fit_scale and not _spans_processes(sharding.mesh):
        dev = _put_row_shards(arr, n_pad, sharding)
    else:
        # a host copy of the whole array for the sake of its last rows
        with span("h2d.pad", bytes=int(arr.nbytes), where="host",
                  module=_MODULE, **_shard_attrs(arr, sharding)):
            pad_block = np.broadcast_to(
                arr[:1], (n_pad - n,) + arr.shape[1:]
            )
            arr_p = np.concatenate([arr, pad_block], axis=0)
        inc("sntc_transfer_pad_bytes_total", int(arr.nbytes), where="host")
        dev = _put_sharded(arr_p, sharding)
    if cacheable:
        try:
            ref = weakref.ref(arr)
        except TypeError:  # non-weakref-able array subclass
            return dev
        _DEVICE_CACHE[key] = (ref, dev)
    return dev


def pad_rows(n: int, n_shards: int) -> int:
    """Rows after padding ``n`` up to a multiple of ``n_shards``, then up to
    a shape BUCKET.

    Bucketing rounds the per-shard row count to ~1.6% granularity so nearly
    equal dataset sizes (e.g. the k train splits of a CrossValidator fold
    loop) compile ONE XLA program instead of k — distinct compiled shapes
    are O(log n) overall.  Padded rows carry weight 0 everywhere (the
    masked-row idiom of this module), so results are unchanged.  Disable
    with ``SNTC_SHAPE_BUCKETS=0`` for exact-shape debugging.
    """
    m = ((n + n_shards - 1) // n_shards) * n_shards
    per = m // n_shards
    if per <= 64 or os.environ.get("SNTC_SHAPE_BUCKETS", "1") == "0":
        return m
    q = 1 << (per.bit_length() - 6)  # 1/64 granularity of the leading bit
    per = ((per + q - 1) // q) * q
    return per * n_shards


def shard_batch(mesh: Mesh, *arrays: np.ndarray, axis_name: str = DATA_AXIS):
    """Pad + device_put arrays row-sharded over the mesh.

    Returns ``(*sharded_arrays, weights)`` where ``weights`` is f32 (N,) with
    1.0 on real rows and 0.0 on padding — the masked-row idiom every reduction
    in this framework uses (SURVEY.md §7.2 mitigation for static shapes).
    Padding replicates row 0 (not zeros) so padded rows stay numerically
    benign under ops like log/σ; their weight removes them from results.
    Where the pad happens (:func:`_cached_shard_put`): a host array of
    1 MiB and over is placed from views of its unpadded rows and the short
    shard is padded on its own chip, so a fit-scale matrix is never copied
    on the host; a smaller one is padded by a host copy before the put.

    Where a fit takes its mesh: the mesh gauge
    (``sntc_collective_mesh_devices``) is set here.
    """
    record_mesh_shape(mesh)
    n = arrays[0].shape[0]
    n_shards = mesh.shape[axis_name]
    n_pad = pad_rows(n, n_shards)
    out = []
    for arr in arrays:
        if arr.shape[0] != n:
            raise ValueError("all arrays must share the leading dimension")
        sharding = NamedSharding(
            mesh, P(axis_name, *([None] * (arr.ndim - 1)))
        )
        out.append(_cached_shard_put(arr, n_pad, sharding))
    weights = np.zeros(n_pad, dtype=np.float32)
    weights[:n] = 1.0
    out.append(_put_sharded(weights, NamedSharding(mesh, P(axis_name))))
    return tuple(out)


def shard_weights(
    mesh: Mesh,
    w: np.ndarray,
    n_padded: int,
    axis_name: str = DATA_AXIS,
):
    """Row weights padded with zeros to ``n_padded`` and sharded over the
    mesh — the companion of :func:`shard_batch` when callers carry their own
    weight column (user weights × padding mask in one array)."""
    w_pad = np.zeros(n_padded, dtype=np.float32)
    w_pad[: len(w)] = w
    return _put_sharded(w_pad, NamedSharding(mesh, P(axis_name)))


def _shrunk_axis_size(survivors: int, n_pad: int) -> int:
    """Largest power-of-two shard count ≤ ``survivors`` that the padded
    batch still divides over.  Power-of-two steps keep every
    shape-bucketed padding (always a multiple of the ORIGINAL shard
    count, itself a power of two on the target topologies) divisible
    without re-padding; 1 always qualifies."""
    c = 1 << max(0, survivors.bit_length() - 1)
    while c > 1 and n_pad % c:
        c //= 2
    return max(1, c)


def make_tree_aggregate(
    fn: Callable,
    mesh: Mesh,
    axis_name: str = DATA_AXIS,
    check_vma: bool = True,
    replicated_args: tuple = (),
    op: str = "tree_aggregate",
) -> Callable:
    """Build a jitted ``agg(*arrays) -> pytree`` that computes
    ``psum_over_shards(fn(shard_of(*arrays)))``.

    ``fn`` takes row-shards (leading axis = local rows) and returns a pytree
    of fixed-shape partials; every leaf is summed across the mesh axis.
    The result is replicated on all devices (the driver-side combOp result,
    but living on-device).  Argument positions in ``replicated_args`` are
    NOT row-sharded — every shard sees them whole (per-call constants like
    bin edges; passing them as arguments instead of closing over them keeps
    one compiled program across calls).

    **Additivity contract:** ``fn``'s output must be an additive sum-tree
    over row partitions (``fn(rows) == fn(rows[:k]) + fn(rows[k:])`` leafwise)
    — true of every aggregate in this framework (moments, gram matrices,
    gradients, histograms, counts) and REQUIRED by the ``device_oom``
    responder, which splits the padded batch into shard-aligned halves and
    sums the two partial trees.

    ``op`` labels this aggregate's ``sntc_collective_*`` evidence series.

    NOTE each call builds a fresh ``jit`` wrapper with its own compile
    cache: callers that aggregate repeatedly (every estimator ``fit``)
    must build ONCE and reuse — on a TPU a rebuilt wrapper recompiles the
    whole program per call (~8 s observed for the scaler's moments pass).
    """
    state = {"mesh": mesh, "resized": False}
    programs: dict = {}
    record_mesh_shape(mesh)

    def _program(m: Mesh):
        prog = programs.get(m)
        if prog is None:

            def agg(*arrays):
                in_specs = tuple(
                    P() if i in replicated_args
                    else P(axis_name, *([None] * (a.ndim - 1)))
                    for i, a in enumerate(arrays)
                )
                return map_reduce_at(
                    m, fn, axis_name=axis_name, in_specs=in_specs,
                    check_vma=check_vma,
                )(*arrays)

            prog = jax.jit(agg)
            programs[m] = prog
        return prog

    def _row_spec(a) -> P:
        return P(axis_name, *([None] * (a.ndim - 1)))

    def _place_on(m: Mesh, arrays: tuple) -> tuple:
        """Re-place a batch on mesh ``m`` (host round trip for the
        row-sharded arrays — acceptable under the duress paths that
        need it, and every byte lands in the transfer ledgers)."""
        out = []
        for i, a in enumerate(arrays):
            spec = P() if i in replicated_args else _row_spec(a)
            out.append(_put_sharded(np.asarray(a), NamedSharding(m, spec)))
        return tuple(out)

    def _ensure_on(m: Mesh, arrays: tuple) -> tuple:
        """After a resize, batches sharded on the ORIGINAL mesh by an
        earlier :func:`shard_batch` still arrive here — detect the
        mismatch and migrate them onto the live mesh."""
        if not state["resized"]:
            return arrays
        live = tuple(np.asarray(m.devices).flat)
        for a in arrays:
            sh = getattr(a, "sharding", None)
            msh = getattr(sh, "mesh", None)
            if msh is not None and tuple(np.asarray(msh.devices).flat) != live:
                return _place_on(m, arrays)
        return arrays

    def _oom_depth_limit() -> int:
        dom = get_collective_domain()
        if dom is not None:
            return dom.policy.oom_split_depth
        return int_from_env("SNTC_COLLECTIVE_OOM_DEPTH", 4, minimum=1)

    def _resize(exc: BaseException, arrays: tuple) -> tuple:
        """The elastic response to a participant dropping out: shrink
        the data axis, re-place the batch on the survivors, journal the
        ``mesh_resize`` decision.  Raises ``exc`` when a resize is not
        possible (1-device mesh, disabled, multi-host)."""
        old = state["mesh"]
        old_n = int(old.shape[axis_name])
        if old_n <= 1 or not _resize_enabled() or _spans_processes(old):
            raise exc
        row_idx = [
            i for i in range(len(arrays)) if i not in replicated_args
        ]
        n_pad = int(arrays[row_idx[0]].shape[0]) if row_idx else 1
        new_n = _shrunk_axis_size(old_n - 1, n_pad)
        fault_point("mesh.resize")
        # survivors = the leading new_n devices of the old mesh along the
        # data axis (faked CPU devices are interchangeable; on real
        # hardware the runtime only names the dead chip after reinit, so
        # the conservative shrink drops the tail of the axis)
        ax = old.axis_names.index(axis_name)
        take = [slice(None)] * old.devices.ndim
        take[ax] = slice(0, new_n)
        new_mesh = Mesh(old.devices[tuple(take)], old.axis_names)
        state["mesh"] = new_mesh
        state["resized"] = True
        try:
            from sntc_tpu.obs.metrics import inc

            inc("sntc_collective_resizes_total")
        except Exception:
            pass
        record_mesh_shape(new_mesh)
        dom = get_collective_domain()
        if dom is not None:
            dom.note_mesh_resize(
                old=old_n, new=new_n, axis=axis_name,
                site="collective.dispatch",
            )
        else:
            from sntc_tpu.resilience import emit_event

            emit_event(
                event="mesh_resize", component="model",
                site="collective.dispatch", axis=axis_name,
                old=old_n, new=new_n,
            )
        return _place_on(new_mesh, arrays)

    def _split(arrays: tuple, depth: int, exc: BaseException):
        """The ``device_oom`` responder: split the padded batch into two
        shard-aligned row halves and SUM their partial trees (valid by
        the additivity contract).  Shard-aligned means each half's row
        count stays divisible by the live shard count, so both halves
        dispatch through the same per-mesh program family."""
        m = state["mesh"]
        n_shards = int(m.shape[axis_name])
        row_idx = [
            i for i in range(len(arrays)) if i not in replicated_args
        ]
        if not row_idx or depth >= _oom_depth_limit():
            raise exc
        n_pad = int(arrays[row_idx[0]].shape[0])
        if n_pad < 2 * n_shards:
            raise exc  # already at one row-block per shard
        cut = ((n_pad // 2 + n_shards - 1) // n_shards) * n_shards
        host = {i: np.asarray(arrays[i]) for i in row_idx}
        halves = []
        for sl in (slice(0, cut), slice(cut, n_pad)):
            part = list(arrays)
            for i in row_idx:
                a = host[i][sl]
                part[i] = _put_sharded(
                    a, NamedSharding(m, _row_spec(a))
                )
            halves.append(tuple(part))
        dom = get_collective_domain()
        if dom is not None:
            dom.note_oom_split(
                rows=n_pad, depth=depth + 1, bucket_floor=n_shards
            )
        out = _run(halves[0], depth + 1)
        out2 = _run(halves[1], depth + 1)
        return jax.tree.map(lambda a, b: a + b, out, out2)

    def _run(arrays: tuple, depth: int = 0):
        from sntc_tpu.resilience.device import classify_device_error

        m = state["mesh"]
        arrays = _ensure_on(m, arrays)
        try:
            fault_point("collective.dispatch")
            out = _program(m)(*arrays)
        except Exception as e:  # noqa: BLE001 — classified below
            kind = classify_device_error(e) if m is not None else None
            if kind == "device_lost":
                return _run(_resize(e, arrays), depth)
            if kind == "device_oom":
                return _split(arrays, depth, e)
            raise
        # mesh=None is the unit-test stub shape (jit monkeypatched out);
        # a real dispatch always has a mesh
        n_shards = int(m.shape[axis_name]) if m is not None else 1
        record_collective(op, axis_name, n_shards, payload_nbytes(out))
        return out

    # resolved ONCE at build time: dispatch runs per optimizer iteration
    # and per streaming batch — thousands of calls per fit must not each
    # re-parse the env and rebuild a policy
    policy = _dispatch_policy()
    breaker = _dispatch_breaker()

    def dispatch(*arrays):
        # the fault/retry/breaker hooks live OUTSIDE the jit so they run
        # per call (inside the trace they would fire once, at compile time)
        def attempt():
            return _run(tuple(arrays))

        if breaker is not None and not breaker.allow():
            raise CircuitOpenError(
                "collective.dispatch", breaker.retry_after_s()
            )
        try:
            if policy is None:
                out = attempt()
            else:
                out = with_retries(
                    attempt, policy, site="collective.dispatch"
                )
        except Exception:
            # KeyboardInterrupt/SystemExit pass through uncounted — a
            # user interrupt is not evidence the backend is down
            if breaker is not None:
                breaker.record_failure()
            raise
        if breaker is not None:
            breaker.record_success()
        return out

    dispatch.mesh = lambda: state["mesh"]  # type: ignore[attr-defined]
    return dispatch


def tree_aggregate(fn: Callable, mesh: Mesh, *arrays, axis_name: str = DATA_AXIS):
    """One-shot convenience over :func:`make_tree_aggregate` (recompiles per
    call site — estimators with iteration loops should build once)."""
    return make_tree_aggregate(fn, mesh, axis_name)(*arrays)
