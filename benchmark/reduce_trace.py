"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to numbers.

Reads the file with ``jax.profiler.ProfileData`` alone.  Of each device plane
(``/device:TPU:<n>``) it takes the ``XLA Ops`` line (one event per executed
operation, nested events included) and the ``XLA Modules`` line (one event
per executed program); of the host planes the benchmark's own spans
(``bench:<name>``, written by ``run.py`` with ``TraceAnnotation``).

* busy: the union of the operations' intervals, inside the ``bench:window``
  span when the trace has one, averaged over the chips used;
* device time per name: an operation's SELF time (its interval minus the
  nested operations it encloses), so that a ``while`` does not count its
  body twice;
* device time per program: the summed durations of the ``XLA Modules``
  events of one program name (``jit__grow_fused``), inside the window;
* idle gaps: the longest intervals of the window in which no operation ran,
  labelled ``<innermost bench span>:<program before>-><program after>``.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def union_length(intervals, lo=None, hi=None) -> float:
    """Total length of the union of ``(start, end)`` intervals, clipped to
    ``[lo, hi]`` when given."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if lo is not None:
            s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """``(start, end)`` of every stretch of ``[lo, hi]`` no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def self_times(events) -> dict:
    """``{name: seconds}`` of self time: events sorted by start; an event
    nested in another is subtracted from its innermost encloser."""
    out = {}
    stack = []  # (name, end, child_ns)

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, start, child = stack.pop()
            out[name] = out.get(name, 0.0) + (end - start - child)
            if stack:
                stack[-1][3] += end - start

    for name, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        close(s)
        stack.append([name, e, s, 0.0])
    close(float("inf"))
    return {k: v / 1e9 for k, v in out.items()}


def host_spans(data):
    spans = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name[len(SPAN_PREFIX):],
                                  float(ev.start_ns),
                                  float(ev.start_ns + ev.duration_ns)))
    return spans


def reduce_file(path: str, n_chips: int = 1) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans = host_spans(data)
    window = [s for s in spans if s[0] == "window"]
    devices = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = _events(line)
            elif line.name == MODULES_LINE:
                modules = _events(line)
        devices[int(m.group(1))] = (ops, modules)
    used = sorted(devices)[:n_chips]
    all_ops = [ev for d in used for ev in devices[d][0]]
    if window:
        lo, hi = window[0][1], window[0][2]
    elif all_ops:
        lo = min(s for _, s, _ in all_ops)
        hi = max(e for _, _, e in all_ops)
    else:
        lo = hi = 0.0
    busy = [union_length([(s, e) for _, s, e in devices[d][0]], lo, hi)
            for d in used]
    busy_s = (sum(busy) / len(busy) / 1e9) if busy else 0.0
    per_name = {}
    for d in used:
        for name, sec in self_times(
            [(n, max(s, lo), min(e, hi)) for n, s, e in devices[d][0]
             if min(e, hi) > max(s, lo)]
        ).items():
            per_name[name] = per_name.get(name, 0.0) + sec / len(used)
    per_module = {}
    for d in used:
        for name, s, e in devices[d][1]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                key = _short(name)
                per_module[key] = per_module.get(key, 0.0) + (e - s) / 1e9 / len(used)
    idle = []
    if used:
        ops0, mods0 = devices[used[0]]
        inner = [s for s in spans if s[0] != "window"]
        for s, e in gaps([(a, b) for _, a, b in ops0], lo, hi):
            mid = 0.5 * (s + e)
            cover = [sp for sp in inner if sp[1] <= mid <= sp[2]]
            label = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover else "between_passes"
            before = [m for m in mods0 if m[2] <= s + 1]
            after = [m for m in mods0 if m[1] >= e - 1]
            prev_m = max(before, key=lambda m: m[2])[0] if before else "start"
            next_m = min(after, key=lambda m: m[1])[0] if after else "end"
            idle.append([f"{label}:{_short(prev_m)}->{_short(next_m)}",
                         (e - s) / 1e9])
    idle.sort(key=lambda t: -t[1])
    ops_sorted = sorted(([_label(k), v] for k, v in per_name.items()),
                        key=lambda t: -t[1])
    return {
        "path": path,
        "busy_s": busy_s,
        "window_s": (hi - lo) / 1e9,
        "device_seconds_by_name": per_name,
        "device_seconds_by_module": per_module,
        "device_ops": ops_sorted,
        "idle_gaps": idle,
        "n_device_planes": len(devices),
        "spans": [[n, (e - s) / 1e9] for n, s, e in spans],
    }


def _label(op: str, width: int = 150) -> str:
    """An operation's HLO text cut to ``width`` characters, layouts
    (``{...}``) dropped: enough to tell the operation and its shapes."""
    return re.sub(r"\{[^{}]*\}", "", op)[:width]


def _short(module_name: str) -> str:
    """``jit__mlp_optimize(123)`` -> ``jit__mlp_optimize``."""
    return re.sub(r"\(\d+\)$", "", module_name)[:60]


def reduce_dir(trace_dir: str, n_chips: int = 1) -> dict:
    return reduce_file(find_xplane(trace_dir), n_chips)


def kernel_seconds(trace: dict, pattern: str, by: str = "name"):
    """Summed device time of the operations (``by="name"``: self time) or
    programs (``by="module"``) whose name matches ``pattern`` (a regular
    expression); ``None`` when none does."""
    rx = re.compile(pattern)
    hits = [v for k, v in trace["device_seconds_by_" + by].items()
            if rx.search(k)]
    return sum(hits) if hits else None


if __name__ == "__main__":
    import json
    import sys

    out = reduce_file(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 1)
    out["device_ops"] = out["device_ops"][:25]
    out["idle_gaps"] = out["idle_gaps"][:25]
    out.pop("device_seconds_by_name")
    print(json.dumps(out, indent=1))
