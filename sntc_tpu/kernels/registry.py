"""The serving-kernel capability registry (r21).

Every hand-written Pallas kernel in this codebase is declared here as a
:class:`KernelSpec` — name, owning module, fit-guard, lowered-jnp twin
tolerance, and fallback story — the same single-source-of-truth
discipline the ``fuse/registry.py`` ``device_fn`` table applies to
fusible stages (``sntc_tpu.fuse.registry.device_kernels`` re-exports
this table as the kernel half of the capability registry).
``scripts/check_kernel_registry.py`` pins registry ⇔
docs/PERFORMANCE.md kernel-forge table ⇔ interpret-mode tests in
tier-1, both directions.

Selection and survival are shared, not per-kernel ad hoc:

* :func:`resolve_serve_kernels` is the one env switch for the serving
  tier — ``SNTC_SERVE_KERNELS`` = ``auto`` (pallas on TPU, off
  elsewhere) / ``pallas`` / ``interpret`` (the CPU tier-1 mode: every
  kernel runs through the Pallas interpreter) / ``off``.  The fit-side
  ``tree_hist`` is registered here for its guard, twin and smoke case
  only: its selection is ``ops.pallas_histogram.tree_hist_impl``
  (backend, mesh, guard; ``SNTC_TREE_HIST``), not this switch.

* :func:`kernel_dispatch` is the poison/fallback ladder for host-level
  kernel calls: a fresh (kernel, signature) crosses the
  ``kernel.compile`` fault boundary; a compile failure — injected or
  genuine — poisons exactly that signature onto the XLA twin path and
  serves the batch there, so under ``auto`` a kernel that cannot
  compile never surfaces an error to the serving engine (zero
  quarantines, zero tenant strikes; the r18 platform-fault contract).
  The poison is counted (``sntc_kernel_poisoned_signatures``,
  ``sntc_kernel_fallback_total{reason=compile_error}``) and
  ``chip_smoke.py`` fails on it; with ``SNTC_SERVE_KERNELS=pallas``
  asked for by name the failure raises instead.  Under an active
  trace (a kernel embedded in a fused program) the decision is made at
  trace time and the in-flight kernel signatures are logged so
  ``FusedSegment.transform_async`` can poison them and recompile the
  SAME fused signature on the pure-XLA path when the enclosing compile
  fails (``sntc_tpu/fuse/planner.py``).

Every decision is counted in the catalogued ``sntc_kernel_*`` metric
family (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import os
import re
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

#: check_kernel_registry.py fails tier-1 when a Pallas call site
#: appears outside a registered kernel's module (or a registered
#: kernel's module has no Pallas call site)
_SERVE_ENV = "SNTC_SERVE_KERNELS"
#: spellings of the env value that ask for the compiled kernels by name
_FORCED = ("pallas", "on", "1", "true")


@dataclass(frozen=True)
class KernelSpec:
    """One registered Pallas kernel (the docs kernel-forge table row)."""

    name: str
    #: repo-relative module holding the Pallas call site
    module: str
    #: fit-guard callable name (documented) + the guard itself
    guard_name: str
    guard: Callable[..., bool]
    #: documented pinning tolerance vs the lowered-jnp twin
    tolerance: str
    #: documented fallback path when the guard rejects / compile poisons
    fallback: str
    #: env switch that selects this kernel (shared or kernel-specific)
    env: str = _SERVE_ENV
    #: ``smoke_case(rows) -> (kernel_fn, twin_fn, args, rtol)``: the
    #: kernel (``kernel_fn(*args, interpret=...)``) and its twin
    #: (``twin_fn(*args)``) over the same seeded inputs at the widths
    #: ``chip_smoke.py`` serves.  Tier-1 cross-lowers ``kernel_fn`` for
    #: TPU from it (tests/test_kernels.py); the smoke runs both on the
    #: chip and compares at ``rtol`` (0 = bitwise).
    smoke_case: Optional[Callable[[int], tuple]] = None


_KERNELS: Dict[str, KernelSpec] = {}
_lock = threading.Lock()

# poison ledger: (kernel name, signature) pairs that failed to compile
# and serve the XLA twin forever after (cleared only by process restart
# — a kernel that cannot compile once will not compile again)
_poisoned: Dict[Tuple[str, Any], str] = {}
# fresh-signature ledger: the kernel.compile fault boundary fires once
# per (kernel, signature), exactly like predict.compile fires once per
# fresh row shape
_seen_sigs: set = set()
# trace-time kernel log (thread-local): kernels armed inside an active
# jit trace, so the fused-program compile-failure handler knows WHICH
# kernel signatures to poison before retrying on pure XLA
_trace_log = threading.local()


def register_kernel(spec: KernelSpec) -> KernelSpec:
    with _lock:
        _KERNELS[spec.name] = spec
    return spec


def _ensure_registered() -> None:
    """Import every kernel-bearing module so the registry is complete
    regardless of which subsystem imported first (the drift check and
    the docs table enumerate through this)."""
    import sntc_tpu.kernels.assemble  # noqa: F401
    import sntc_tpu.kernels.forest  # noqa: F401
    import sntc_tpu.ops.pallas_histogram  # noqa: F401


def registered_kernels() -> Dict[str, KernelSpec]:
    _ensure_registered()
    with _lock:
        return dict(_KERNELS)


def kernel_spec(name: str) -> KernelSpec:
    _ensure_registered()
    return _KERNELS[name]


# -- selection ---------------------------------------------------------------


def resolve_serve_kernels() -> str:
    """The serving-tier mode: ``pallas`` / ``interpret`` / ``off``.

    ``SNTC_SERVE_KERNELS`` = ``auto`` (default: pallas on a TPU default
    backend, off elsewhere — the CPU interpreter is a correctness tool,
    not a fast path), ``pallas`` (force), ``interpret`` (run every
    kernel through the Pallas interpreter — the tier-1 CPU mode), or
    ``off``."""
    raw = os.environ.get(_SERVE_ENV, "auto").strip().lower()
    if raw in ("off", "0", "none", "false"):
        return "off"
    if raw == "interpret":
        return "interpret"
    if raw in _FORCED:
        return "pallas"
    import jax

    return "pallas" if jax.default_backend() == "tpu" else "off"


def serve_kernels_forced() -> bool:
    """True when ``SNTC_SERVE_KERNELS`` names ``pallas`` outright: the
    caller asked for the compiled kernels, so one that cannot compile
    is an error to raise, not a signature to poison onto its twin."""
    return os.environ.get(_SERVE_ENV, "auto").strip().lower() in _FORCED


def resolve_impl(name: str, **guard_kwargs) -> str:
    """Implementation selection for the serve-tier kernel ``name``
    through the shared switch and its guard.  Returns the impl token
    the caller dispatches on; a fallback is counted into the
    ``sntc_kernel_*`` family here, a dispatch by the caller."""
    from sntc_tpu.obs.metrics import inc

    spec = kernel_spec(name)
    mode = resolve_serve_kernels()
    if mode == "off":
        inc("sntc_kernel_fallback_total", kernel=name, reason="off")
        return "xla"
    if not spec.guard(**guard_kwargs):
        inc("sntc_kernel_fallback_total", kernel=name, reason="guard")
        return "xla"
    return mode  # "pallas" | "interpret"


# -- the poison ladder -------------------------------------------------------


def poisoned(name: str, sig) -> bool:
    with _lock:
        return (name, sig) in _poisoned


def poison(name: str, sig, reason: str) -> bool:
    """Poison (kernel, signature) onto the XLA twin path; returns True
    when fresh.  Counted live in ``sntc_kernel_poisoned_signatures``
    and journaled as a structured event (never a tenant strike)."""
    from sntc_tpu.obs.metrics import set_gauge
    from sntc_tpu.resilience.policy import emit_event

    with _lock:
        fresh = (name, sig) not in _poisoned
        _poisoned[(name, sig)] = reason
        count = len(_poisoned)
    if fresh:
        try:
            set_gauge("sntc_kernel_poisoned_signatures", count)
        except Exception:
            pass
        emit_event(
            event="kernel_poisoned", component="model",
            site="kernel.compile", kernel=name, signature=repr(sig),
            reason=reason,
        )
    return fresh


def clear_poisons() -> None:
    """Test hook: forget every poisoned kernel signature."""
    from sntc_tpu.obs.metrics import set_gauge

    with _lock:
        _poisoned.clear()
        _seen_sigs.clear()
    try:
        set_gauge("sntc_kernel_poisoned_signatures", 0)
    except Exception:
        pass


def kernel_stats() -> dict:
    """Evidence snapshot for bench/fusion_stats: current mode plus the
    poison ledger."""
    with _lock:
        return {
            "mode": resolve_serve_kernels(),
            "poisoned_signatures": len(_poisoned),
            "poisoned": {
                f"{k}:{s}": r for (k, s), r in _poisoned.items()
            },
        }


def _under_trace(args) -> bool:
    import jax

    return any(isinstance(a, jax.core.Tracer) for a in args)


def begin_trace_capture(sharded: bool = False) -> None:
    """Planner hook: start logging kernels armed inside the fused
    trace about to run on this thread.  ``sharded`` says the dispatch
    placed its rows over a serve mesh: Mosaic kernels cannot be
    partitioned by GSPMD (a ``pallas_call`` must sit inside a
    per-shard map — ``parallel.mesh.map_at``), and the kernel tier is
    single-device, so kernels
    in such a trace take their twins — counted ``reason="mesh"``, a
    declared path rather than a compile failure for the poison ladder
    to absorb."""
    _trace_log.entries = []
    _trace_log.sharded = bool(sharded)


def traced_kernels() -> List[Tuple[str, Any]]:
    return list(getattr(_trace_log, "entries", []))


def poison_traced(reason: str) -> int:
    """Poison every kernel signature the current thread's last fused
    trace armed (the enclosing fused program failed to compile).
    Returns the number poisoned — 0 means no kernel was involved and
    the failure belongs to the fused program itself."""
    entries = traced_kernels()
    for name, sig in entries:
        poison(name, sig, reason)
    _trace_log.entries = []
    return len(entries)


def _note_trace(name: str, sig) -> None:
    entries = getattr(_trace_log, "entries", None)
    if entries is None:
        entries = _trace_log.entries = []
    entries.append((name, sig))


_PALLAS_COMPILE_RE = re.compile(
    r"interpret mode is supported|mosaic|pallas|tpu kernel compiler",
    re.IGNORECASE,
)


def classify_kernel_error(exc: Optional[BaseException]) -> Optional[str]:
    """Kernel-scope widening of ``classify_device_error``: inside the
    kernel tier's own dispatch (or a fused trace that armed kernels), a
    Pallas/Mosaic lowering failure is a compile error even when it is
    not XLA-runtime-shaped — e.g. the CPU backend raises a plain
    ``ValueError("Only interpret mode is supported on CPU backend.")``
    for a compiled kernel off-TPU.  Such a failure poisons the
    signature and serves the twin (or raises when the kernels were
    forced), never strikes the tenant.  The strict classifier keeps its
    shape rules for every other scope (a user ``ValueError`` mentioning
    "pallas" outside the kernel tier must never flip serving paths),
    which is why this widening lives here and not in
    ``resilience.device``."""
    from sntc_tpu.resilience.device import classify_device_error

    kind = classify_device_error(exc)
    if kind is not None:
        return kind
    seen = 0
    while exc is not None and seen < 8:
        if _PALLAS_COMPILE_RE.search(str(exc)):
            return "compile_error"
        exc = exc.__cause__ or exc.__context__
        seen += 1
    return None


def kernel_dispatch(
    name: str,
    kernel_fn: Callable[[str], Any],
    twin_fn: Callable[[], Any],
    *,
    signature,
    guard_kwargs: Optional[dict] = None,
):
    """Serve one kernel-tier call through the selection + poison
    ladder.  ``kernel_fn(impl)`` runs the Pallas path (``impl`` is
    ``"pallas"`` or ``"interpret"``); ``twin_fn()`` is the lowered-jnp
    XLA twin the kernel is pinned against (bitwise f64, ≤1e-5 rel f32 —
    docs/PERFORMANCE.md kernel-forge table).

    Host-level calls get the full try/poison/fallback arc: a compile
    failure (injected at ``kernel.compile`` or genuine) poisons exactly
    (kernel, signature) and serves THIS call on the twin — nothing
    escapes to the engine's strike ladder — unless the kernels were
    forced (:func:`serve_kernels_forced`), where it raises.  Calls
    under an active jit trace decide at trace time and log the armed
    signature for the planner's compile-failure handler;
    OOM/device-lost errors re-raise (they belong to the predictor's r18
    response ladder, not the kernel tier)."""
    from sntc_tpu.obs.metrics import inc
    from sntc_tpu.resilience.faults import fault_point

    impl = resolve_impl(name, **(guard_kwargs or {}))
    if impl not in ("pallas", "interpret"):
        return twin_fn()
    if poisoned(name, signature):
        inc("sntc_kernel_fallback_total", kernel=name, reason="poisoned")
        return twin_fn()
    with _lock:
        fresh = (name, signature) not in _seen_sigs
        _seen_sigs.add((name, signature))
    traced = _under_trace(
        signature if isinstance(signature, (list, tuple)) else ()
    )
    # the kernel-compile fault boundary: fires once per fresh
    # (kernel, signature), exactly like predict.compile per row shape.
    # Under a trace this raises INTO the enclosing fused compile, where
    # the planner poisons the logged kernel and retries on pure XLA.
    try:
        if fresh:
            fault_point("kernel.compile")
        out = kernel_fn(impl)
    except Exception as e:
        kind = classify_kernel_error(e)
        if kind != "compile_error" or traced or serve_kernels_forced():
            raise
        poison(name, signature, repr(e))
        inc(
            "sntc_kernel_fallback_total", kernel=name,
            reason="compile_error",
        )
        return twin_fn()
    inc("sntc_kernel_dispatch_total", kernel=name, impl=impl)
    return out


def serve_kernel_call(
    name: str,
    args: tuple,
    kernel_fn: Callable[[str], Any],
    twin_fn: Callable[[], Any],
    *,
    static: tuple = (),
    guard_kwargs: Optional[dict] = None,
):
    """The model-serve entry: build the (shape, dtype, static) kernel
    signature from ``args`` — tracers and concrete arrays alike carry
    shape/dtype — then dispatch.  Inside a fused trace the decision is
    static per enclosing compile: log the armed kernel so a failed
    fused compile can poison it and retrace on the twin."""
    sig = tuple(
        (tuple(a.shape), str(getattr(a, "dtype", type(a).__name__)))
        for a in args
    ) + tuple(static)
    if _under_trace(args):
        from sntc_tpu.obs.metrics import inc

        impl = resolve_impl(name, **(guard_kwargs or {}))
        if impl not in ("pallas", "interpret") or poisoned(name, sig):
            return twin_fn()
        if getattr(_trace_log, "sharded", False):
            inc("sntc_kernel_fallback_total", kernel=name, reason="mesh")
            return twin_fn()
        _note_trace(name, sig)
        with _lock:
            fresh = (name, sig) not in _seen_sigs
            _seen_sigs.add((name, sig))
        if fresh:
            from sntc_tpu.resilience.faults import fault_point

            fault_point("kernel.compile")
        inc("sntc_kernel_dispatch_total", kernel=name, impl=impl)
        return kernel_fn(impl)
    return kernel_dispatch(
        name, kernel_fn, twin_fn, signature=sig,
        guard_kwargs=guard_kwargs,
    )
