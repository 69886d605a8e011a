"""Shared arithmetic of the yardstick: the table of peaks and the roofline.

The work one pass needs (operations and bytes, from shapes alone) is counted
by the configuration's estimator adapter (``benchmark/estimators/<name>.py``:
``work_fit`` / ``work_evaluate``, with ``PROGRAMS``, the names of the layer's
own device programs); this file turns work and a traced device time into a
share of the chip's peak.  Work is what the ALGORITHM needs, never what an
implementation happens to do: one ``value_and_grad`` per L-BFGS iteration
(line-search re-evaluations are recomputation), one read of the binned matrix
per tree level, no multiplier for a higher matmul precision.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of ``device_kind``; an unknown device is an
    error, and there is no override."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}: add a row, with its "
            "source, to benchmark/peaks.json"
        )
    return table[device_kind]


def roofline_seconds(work: dict, peaks: dict, n_chips: int = 1) -> float:
    """The least time the chips could take for ``work = {"flops", "bytes"}``:
    the larger of operations over peak FLOP/s and bytes over peak bytes/s."""
    return max(
        work["flops"] / (peaks["flops_per_s"] * n_chips),
        work["bytes"] / (peaks["bytes_per_s"] * n_chips),
    )


def share_percent(work: dict, seconds: float, peaks: dict,
                  n_chips: int = 1):
    """``roofline_seconds / seconds`` in percent; ``None`` without a time."""
    if not seconds or seconds <= 0:
        return None
    return 100.0 * roofline_seconds(work, peaks, n_chips) / seconds


def program_share(ctx: dict, kind: str):
    """Share of the chip's peak that the work of one pass of ``kind`` (the
    adapter's ``work_<kind>``, from shapes) makes of the DEVICE time of the
    layer's own programs in the trace (the adapter's ``PROGRAMS[kind]``, a
    regular expression over the ``XLA Modules`` names), per traced pass; what
    ``fit_mfu`` and ``eval_mfu`` report.  ``None`` when the trace holds none
    of those programs (a CPU rehearsal, a renamed program): never 0."""
    import reduce_trace

    trace, adapter = ctx.get("trace"), ctx["adapter"]
    pattern = getattr(adapter, "PROGRAMS", {}).get(kind)
    if not trace or not ctx["passes"] or not pattern:
        return None
    seconds = reduce_trace.kernel_seconds(trace, pattern, by="module")
    if not seconds:
        return None
    need = getattr(adapter, "work_" + kind)(
        ctx["cfg"], ctx["rows"], ctx.get("pass_info", {})
    )
    return share_percent(need, seconds / len(ctx["passes"]), ctx["peaks"],
                         ctx["cell"]["chips"])


def idle_share(ctx: dict):
    """100 * (1 - device busy / traced window); ``None`` without a device
    plane in the trace (a CPU rehearsal)."""
    trace = ctx.get("trace")
    if not trace or not trace["window_s"] or trace["n_device_planes"] == 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
