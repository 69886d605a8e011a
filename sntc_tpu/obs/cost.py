"""MFU/roofline evidence plane (r21).

PR 4's fusion planner grew an opt-in ``SNTC_OBS_COST_ANALYSIS`` hook
that stashed XLA's own per-program FLOPs/bytes estimate next to each
compiled signature.  This module promotes that hook into a shared
plane: :func:`extract` pulls the cost estimate from any compiled jit
program, and :func:`roofline` combines it with measured wall time and
the device's peaks (:func:`probed_peaks`) into achieved-vs-peak
numbers —

    achieved FLOP/s  = flops x invocations / seconds
    MFU              = achieved FLOP/s / peak FLOP/s
    BW utilization   = achieved bytes/s / peak bytes/s
    arithmetic intensity = flops / bytes accessed

surfaced three ways: the catalogued ``sntc_mfu_*`` gauges (per serving
segment), the ``roofline`` block of ``fuse.fusion_stats()``, and
``bench.py --mfu`` / bench config 16's per-segment evidence.  Every
number carries the peaks' ``peak_source`` (datasheet / estimate / env)
so a CPU MFU is never mistaken for a measured-chip figure.

The hook stays opt-in: extraction forces an eager compile and the
dispatch timing adds a clock read per batch, so the planner only pays
for either when ``SNTC_OBS_COST_ANALYSIS`` is set.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

#: the cost_analysis() keys worth keeping (XLA emits dozens)
_KEYS = ("flops", "bytes accessed", "transcendentals")

#: ``device_kind`` -> (peak FLOP/s, peak memory bytes/s, source) — the
#: roofline denominators.  The TPU row is the published peak of one
#: v5e chip (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
#: 819 GB/s HBM; JAX reports the chip as "TPU v5 lite"); the CPU row is
#: an order-of-magnitude ESTIMATE so CPU figures are honest about their
#: provenance (``peak_source`` travels with every number).  A device
#: that is not here is an error, not a default.
_PEAK_TABLE = {
    "TPU v5 lite": (1.97e14, 8.19e11, "datasheet"),
    "cpu": (2.0e11, 5.0e10, "estimate"),
}


def probed_peaks(device_kind: Optional[str] = None) -> dict:
    """Peak FLOP/s and memory bandwidth for ``device_kind`` (default:
    ``jax.devices()[0].device_kind``).

    ``SNTC_PEAK_FLOPS`` / ``SNTC_PEAK_BW`` override the static table
    (measured numbers from a real chip beat any datasheet); overrides
    flip ``peak_source`` to ``"env"``.  An unknown device raises — a
    roofline against another chip's peak is worse than none."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    if device_kind not in _PEAK_TABLE:
        raise KeyError(
            f"no peak FLOP/s / bandwidth row for device_kind "
            f"{device_kind!r}: add it, with its source, to "
            "sntc_tpu.obs.cost._PEAK_TABLE"
        )
    flops, bw, source = _PEAK_TABLE[device_kind]
    env_f = os.environ.get("SNTC_PEAK_FLOPS")
    env_b = os.environ.get("SNTC_PEAK_BW")
    if env_f:
        flops = float(env_f)
        source = "env"
    if env_b:
        bw = float(env_b)
        source = "env"
    return {
        "device_kind": device_kind,
        "flops": flops,
        "bw": bw,
        "peak_source": source,
    }


def enabled() -> bool:
    """True when the opt-in cost/roofline plane is armed."""
    return bool(os.environ.get("SNTC_OBS_COST_ANALYSIS"))


def extract(prog, args) -> Optional[Dict[str, float]]:
    """XLA's FLOPs/bytes estimate for ``prog`` lowered at ``args`` —
    the planner hook's body, shared.  Returns ``None`` when the
    backend offers no cost analysis (some platforms don't)."""
    try:
        cost = prog.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return {
            k: float(v)
            for k, v in dict(cost or {}).items()
            if isinstance(v, (int, float)) and k in _KEYS
        }
    except Exception:
        return None


def roofline(
    cost: Optional[Dict[str, float]],
    seconds: float = 0.0,
    invocations: int = 0,
    device_kind: Optional[str] = None,
) -> Optional[Dict[str, Any]]:
    """Achieved-vs-peak accounting for one compiled program.

    ``cost`` is an :func:`extract` result; ``seconds`` is total
    measured wall time across ``invocations`` dispatches of it.  With
    no timing yet (warmup) the static quantities — FLOPs, bytes,
    arithmetic intensity, peaks — still report; the achieved/MFU
    fields appear once there is a nonzero measurement."""
    if not cost:
        return None
    peaks = probed_peaks(device_kind)
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    out: Dict[str, Any] = {
        "flops": flops,
        "bytes_accessed": nbytes,
        "arithmetic_intensity": (flops / nbytes) if nbytes else None,
        "peak_flops": peaks["flops"],
        "peak_bw": peaks["bw"],
        "peak_source": peaks["peak_source"],
        "device_kind": peaks["device_kind"],
        "invocations": int(invocations),
        "seconds": float(seconds),
    }
    if seconds > 0 and invocations > 0:
        achieved_flops = flops * invocations / seconds
        achieved_bw = nbytes * invocations / seconds
        out["achieved_flops"] = achieved_flops
        out["achieved_bw"] = achieved_bw
        out["mfu"] = achieved_flops / peaks["flops"]
        out["bw_util"] = achieved_bw / peaks["bw"]
    return out


def emit_mfu(segment: int, roof: Optional[Dict[str, Any]]) -> None:
    """Publish one segment's roofline onto the catalogued gauges
    (``sntc_mfu_ratio`` / ``sntc_mfu_bw_ratio``, labeled by segment)."""
    if not roof or "mfu" not in roof:
        return
    from sntc_tpu.obs.metrics import set_gauge

    seg = str(segment)
    set_gauge("sntc_mfu_ratio", roof["mfu"], segment=seg)
    set_gauge("sntc_mfu_bw_ratio", roof["bw_util"], segment=seg)
