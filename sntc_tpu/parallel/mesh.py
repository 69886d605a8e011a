"""The mesh substrate — ONE parallelism API for the whole framework.

Replaces Spark's cluster-manager / executor layer (SURVEY.md §1 L8):
instead of ``spark-submit --master local[*]`` placing tasks on executor
JVMs, we build a ``jax.sharding.Mesh`` over the TPU chips of one ICI
domain (v5e-8 target) and run every estimator SPMD over it.  Everything
that shards, maps, or reduces in this codebase goes through this module
(r22): the DrJAX-style primitives :func:`map_at` / :func:`reduce_at` /
:func:`map_reduce_at` express per-shard computation + named-axis
reduction, so sharding is a *deployment decision* (which mesh you pass)
rather than a code path — the five collective call sites
(``parallel/collectives.py``, ``models/kmeans.py``, ``models/lda.py``,
``models/pic.py``, ``models/tree/grower.py``) are all written against
these primitives and never touch ``shard_map``/``pmap`` directly.

Axis names are DECLARED in :data:`MESH_AXES` — the registry is the
single source of truth that ``scripts/check_mesh_axes.py`` drift-checks
against every ``PartitionSpec`` literal in the package and the axis
table in docs/PERFORMANCE.md, both directions.

Mesh construction covers three deployment shapes:

* :func:`default_mesh` — 1-D ``("data",)`` over the visible devices of
  one process (the common case, and the serve plane's shape);
* :func:`make_mesh` — 2-D ``("data", "model")`` within one process;
* :func:`hybrid_mesh` — the multi-host path: DCN-connected processes
  stack along the outer (data) axis, ICI neighbors fill within a host
  (the ``mesh_utils.create_hybrid_device_mesh`` idiom, SNIPPETS.md
  [1]–[3]).

Dev/test: ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` gives
8 fake CPU devices — the ``local[2]``/``local-cluster`` analog
(SURVEY.md §4.1); tier-1 runs the whole sharded plane over them.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sntc_tpu.obs import set_gauge

#: Axis-name registry — every mesh axis the framework may declare, with
#: its role.  ``scripts/check_mesh_axes.py`` enforces that every
#: ``PartitionSpec``/``psum`` axis literal in ``sntc_tpu/`` names a key
#: here, and that the docs/PERFORMANCE.md axis table mirrors this dict
#: exactly (both directions).
MESH_AXES = {
    "data": (
        "batch rows — the RDD-partition analog; batches shard over it, "
        "reductions psum over it (SURVEY.md §5.8)"
    ),
    "model": (
        "parameter shards for wide layers — absent upstream (SURVEY.md "
        "§2.5) but plumbed for the multichip dryrun and future growth"
    ),
}

DATA_AXIS = "data"
MODEL_AXIS = "model"


def device_report() -> dict:
    """The devices this process runs on, as JAX reports them.  Every
    result line the CLI and the bench print carries these fields, so a
    number from a CPU run can never pass for a chip's."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


_device_ready_noted = False


def _note_device_ready() -> None:
    """Set ``sntc_process_device_ready_seconds`` at the process's first
    mesh, the one place every entry point passes before any placement,
    with the devices already in hand: seconds since the kernel started
    the process (``/proc/self/stat`` field 22, on the boot clock), so
    the interpreter's start and the imports are in it.  Where the
    kernel does not say, the gauge stays unset."""
    global _device_ready_noted
    if _device_ready_noted:
        return
    _device_ready_noted = True
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return
    set_gauge("sntc_process_device_ready_seconds", age)


def default_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over (the first ``n_devices``) available devices, axis "data"."""
    devices = jax.devices()
    _note_device_ready()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devices)} available"
            )
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (DATA_AXIS,))


def make_mesh(
    data: int = -1,
    model: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """2-D ``(data, model)`` mesh.  ``data=-1`` means "all remaining devices".

    ``model`` should divide the device count; collectives for gradients ride
    the ``data`` axis, parameter shards the ``model`` axis.
    """
    devs = list(jax.devices() if devices is None else devices)
    _note_device_ready()
    if data == -1:
        if len(devs) % model:
            raise ValueError(f"{len(devs)} devices not divisible by model={model}")
        data = len(devs) // model
    devs = devs[: data * model]
    if len(devs) != data * model:
        raise ValueError(
            f"need {data * model} devices for mesh ({data},{model}), "
            f"have {len(devs)}"
        )
    arr = np.array(devs).reshape(data, model)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def hybrid_mesh(data: int = -1, model: int = 1) -> Mesh:
    """Multi-host ``(data, model)`` mesh: processes stack along the outer
    (data) axis over DCN, ICI neighbors fill within each host — the
    ``mesh_utils.create_hybrid_device_mesh`` construction (SNIPPETS.md
    [1]–[3]), which keeps the model axis inside one ICI domain so
    parameter-shard collectives never cross the slow DCN links.

    Single-process (including the faked-device CPU host) degrades to
    :func:`make_mesh` — the hybrid path needs per-granule device groups
    that only exist with ``jax.distributed`` initialized.
    """
    if jax.process_count() == 1:
        return make_mesh(data=data, model=model)
    n = jax.device_count()
    if data == -1:
        if n % model:
            raise ValueError(f"{n} devices not divisible by model={model}")
        data = n // model
    procs = jax.process_count()
    if data % procs:
        raise ValueError(
            f"data={data} not divisible by process count {procs} — the "
            "hybrid mesh stacks whole processes along the data axis"
        )
    devs = jax.devices()
    _note_device_ready()
    slices = {getattr(d, "slice_index", None) for d in devs}
    if len(slices) > 1 and None not in slices:
        from jax.experimental import mesh_utils

        devices = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=(data // procs, model),
            dcn_mesh_shape=(procs, 1),
        )
        return Mesh(devices, (DATA_AXIS, MODEL_AXIS))
    # no slice structure (faked CPU multi-process, single-slice pods):
    # jax.devices() order is globally consistent and groups each host's
    # devices contiguously, so a plain reshape already yields the
    # ICI-inner / DCN-outer hierarchy the hybrid construction builds
    return Mesh(
        np.array(devs[: data * model]).reshape(data, model),
        (DATA_AXIS, MODEL_AXIS),
    )


def data_sharding(mesh: Mesh, rank: int = 1) -> NamedSharding:
    """Shard the leading (row) axis over "data"; replicate trailing axes."""
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (rank - 1))))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# SPMD primitives — the DrJAX shape: computation is expressed as a *map*
# over a named mesh axis plus a *reduce* over that axis, with the axis
# name declared at the call site.  ``shard_map`` is the lowering detail,
# confined to this module (acceptance: no direct shard_map/pmap call
# sites outside parallel/mesh.py).
# ---------------------------------------------------------------------------


def map_at(
    mesh: Mesh,
    fn: Callable,
    *,
    in_specs,
    out_specs,
    check_vma: bool = True,
    jit: bool = True,
):
    """DrJAX-style *map* primitive: run ``fn`` SPMD over ``mesh`` with the
    given placement specs.  ``fn`` sees per-shard blocks (leading axis =
    local rows for a ``P("data", ...)`` spec) and may call
    :func:`reduce_at` / ``jax.lax.psum`` over any declared mesh axis.

    ``jit=True`` wraps the mapped program in ``jax.jit`` — build ONCE and
    dispatch many (every estimator fit loop); ``jit=False`` returns the
    bare mapped callable for call sites already inside a traced context
    or that rebuild per call (the tree grower's per-level histogram).
    """
    mapped = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )
    return jax.jit(mapped) if jit else mapped


def reduce_at(tree, axis_name: str = DATA_AXIS):
    """DrJAX-style *reduce* primitive: sum every leaf of ``tree`` across
    the named mesh axis.  Valid only inside a :func:`map_at` body (the
    axis must be bound)."""
    return jax.tree.map(lambda t: jax.lax.psum(t, axis_name), tree)


def map_reduce_at(
    mesh: Mesh,
    fn: Callable,
    *,
    axis_name: str = DATA_AXIS,
    in_specs,
    out_specs=P(),
    check_vma: bool = True,
    jit: bool = False,
):
    """``map_at`` + ``reduce_at`` fused: apply ``fn`` per shard and psum
    every output leaf over ``axis_name``; the result is replicated (the
    driver-side combOp result, living on-device).  The building block
    under ``collectives.make_tree_aggregate``."""

    def local(*shards):
        return reduce_at(fn(*shards), axis_name)

    return map_at(
        mesh, local, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma, jit=jit,
    )


def sharded_jit(
    fun: Callable,
    in_shardings=None,
    out_shardings=None,
    **jit_kwargs,
):
    """Partitioned ``jit`` with the t5x-style fallback (SNIPPETS.md [1]):
    on a single-device backend the sharding annotations are dropped and
    ``fun`` is plain-jitted — annotations over a 1-device "mesh" only
    add partitioner overhead.  With >1 device (real TPUs or faked CPU
    devices) the annotations are honored."""
    if jax.device_count() == 1:
        return jax.jit(fun, **jit_kwargs)
    return jax.jit(
        fun, in_shardings=in_shardings, out_shardings=out_shardings,
        **jit_kwargs,
    )


# ---------------------------------------------------------------------------
# evidence plane — every collective dispatch records how often and how
# many bytes crossed the mesh, per (op, axis), extending the
# sntc_transfer_* discipline to the collective layer (SparCML makes
# bytes-moved the quantity compressed reductions must beat; these
# counters are the baseline they will be measured against).
# ---------------------------------------------------------------------------


def collective_wire_bytes(n_shards: int, payload_bytes: int) -> int:
    """Ring all-reduce cost model: reducing a replicated payload of
    ``payload_bytes`` across ``n_shards`` devices moves
    ``2*(n-1)/n * payload`` per device — ``2*(n-1) * payload / n * n``
    total on the wire.  One device moves nothing.  Loop-carried psums
    (a whole Lloyd/IRLS loop inside one program) count ONCE per
    dispatch — the series is a documented lower bound, not a trace."""
    if n_shards <= 1:
        return 0
    return 2 * (n_shards - 1) * int(payload_bytes)


def record_collective(
    op: str, axis_name: str, n_shards: int, payload_bytes: int
) -> None:
    """Host-side evidence for one collective dispatch.  These are python
    counters: call it where the program is DISPATCHED, never inside a
    ``jit``'s trace (there it would count compilations).  Callers:
    ``collectives.make_tree_aggregate`` after each dispatch, the
    dispatch sites of kmeans / lda / pic, and ``grower._level_plan``,
    once a ``grow_forest`` call, with the summed payload of the level
    histograms that call's one program all-reduces."""
    try:
        from sntc_tpu.obs.metrics import inc

        inc("sntc_collective_dispatches_total", op=op, axis=axis_name)
        wire = collective_wire_bytes(n_shards, payload_bytes)
        if wire:
            inc(
                "sntc_collective_bytes_moved_total", wire,
                op=op, axis=axis_name,
            )
    except Exception:
        pass


def record_mesh_shape(mesh: Mesh) -> None:
    """Mirror the mesh shape into the per-axis device gauge."""
    try:
        from sntc_tpu.obs.metrics import set_gauge

        for axis_name, size in dict(mesh.shape).items():
            set_gauge(
                "sntc_collective_mesh_devices", size, axis=axis_name
            )
    except Exception:
        pass


def payload_nbytes(tree) -> int:
    """Total bytes of every leaf in ``tree`` — the reduced-payload size
    fed to :func:`collective_wire_bytes` (callers pass only the
    REPLICATED outputs; shard-local outputs never cross the mesh)."""
    return int(
        sum(getattr(t, "nbytes", 0) for t in jax.tree.leaves(tree))
    )
