"""The device's idle time, split by what the host was doing in it.

The program writes its own spans into the profiler's trace, on the device
trace's clock, as host events named ``sntc:<name>`` (``sntc_tpu.obs.span``
while a profiler session runs), with its attributes as the event's stats.
This file takes the idle intervals of chip 0 inside ``bench:window`` (the
same intervals ``device_idle_share.*`` counts) and gives every instant of
them to the innermost ``sntc:`` span that covers it on the thread that runs
the window.  The instants fall into four groups, a partition of the idle
time:

* ``upload``: inside the placement layer's ``h2d.*`` spans (``h2d.put``, its
  one routing point onto the device, and ``h2d.pad``, the host copy that pads
  the rows to a shard multiple before it);
* ``feature`` / ``models``: the innermost span carries that ``module=``;
* ``unattributed``: everything else (under ``pipeline.fit`` alone, under a
  span of another module, or under no span: the harness's own frame).

``xla.compile`` markers (one per executable built or loaded, see
``sntc_tpu/utils/compile_cache.py``) are counted inside the window on every
thread.  A trace without ``sntc:`` spans (a program from before they existed)
gives ``None`` everywhere; one without a device plane (a CPU rehearsal) gives
``None`` for the idle seconds and still counts compiles.
"""

from __future__ import annotations

import bisect
import sys

import reduce_trace

SPAN_PREFIX = "sntc:"
WINDOW = reduce_trace.SPAN_PREFIX + "window"
UPLOAD_PREFIX, COMPILE = "h2d.", "xla.compile"
GROUPS = ("feature", "models", "upload", "unattributed")
NO_SPAN = "(no span)"


def group_of(name: str, attrs: dict) -> str:
    if name.startswith(UPLOAD_PREFIX):
        return "upload"
    module = attrs.get("module")
    return module if module in ("feature", "models") else "unattributed"


def label_of(name: str, attrs: dict) -> str:
    """``stage.fit[StringIndexer]``, ``d2h.fetch[forest]``, ``rf.bagging``."""
    which = attrs.get("stage") or attrs.get("what")
    return f"{name}[{which}]" if which else name


def innermost(spans):
    """``(stretches, parents)``: ``[(start, end, i)]``, sorted, the stretches
    in which ``spans[i]`` (each ``(name, start, end, attrs)`` of ONE thread,
    so properly nested) is the innermost open span, and the set of ``i``
    that enclose another span.  Stretches no span covers are left out."""
    out, parents, stack, cur = [], set(), [], 0.0  # stack of (i, end)

    def close(upto):
        nonlocal cur
        while stack and stack[-1][1] <= upto:
            i, end = stack.pop()
            if end > cur:
                out.append((cur, end, i))
                cur = end

    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][1], -spans[i][2]))
    for i in order:
        start, end = spans[i][1], spans[i][2]
        close(start)
        if stack:
            top, top_end = stack[-1]
            if end > start:  # a marker does not make its encloser a parent
                parents.add(top)
            if start > cur:
                out.append((cur, start, top))
            end = min(end, top_end)  # a child never outlives its parent
        cur = start
        stack.append((i, end))
    close(float("inf"))
    return out, parents


def attribute(idle, spans, per: int = 1) -> dict:
    """Split the ``idle`` intervals ``[(start, end)]`` (ns) over ``spans``.
    ``{"total_s", "by_group": {group: s}, "by_span": {label: row}}``, seconds
    divided by ``per`` (passes); a row holds the span's group, its idle
    seconds, its own summed duration, its count and whether it is a leaf
    (no span of that label encloses another)."""
    segs, parents = innermost(spans)
    starts = [s for s, _, _ in segs]
    by_group = dict.fromkeys(GROUPS, 0.0)
    rows = {}

    def row_of(label, group):
        return rows.setdefault(label, {"group": group, "idle_s": 0.0,
                                       "span_s": 0.0, "count": 0,
                                       "leaf": True})

    keys = []
    for i, (name, start, end, attrs) in enumerate(spans):
        keys.append((label_of(name, attrs), group_of(name, attrs)))
        row = row_of(*keys[i])
        row["span_s"] += (end - start) / 1e9 / per
        row["count"] += 1
        row["leaf"] = row["leaf"] and i not in parents
    total = 0.0
    for lo, hi in idle:
        if hi <= lo:
            continue
        total += hi - lo
        rest = hi - lo
        k = max(bisect.bisect_right(starts, lo) - 1, 0)
        while k < len(segs) and segs[k][0] < hi:
            a, b, i = segs[k]
            part = min(b, hi) - max(a, lo)
            if part > 0:
                rest -= part
                label, group = keys[i]
                rows[label]["idle_s"] += part / 1e9 / per
                by_group[group] += part / 1e9 / per
            k += 1
        if rest > 0:
            row_of(NO_SPAN, "unattributed")["idle_s"] += rest / 1e9 / per
            by_group["unattributed"] += rest / 1e9 / per
    return {"total_s": total / 1e9 / per, "by_group": by_group,
            "by_span": rows}


def read_trace(path: str, passes: int = 1) -> dict:
    """The table of one traced run of the harness: ``{"idle": attribute(...)
    or None, "compiles": count per pass or None}``."""
    from jax.profiler import ProfileData

    window, window_line, chips = None, None, {}
    lines = {}  # (plane, line) -> [(name, start, end, attrs)]
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        m = reduce_trace.DEVICE_PLANE.match(plane.name)
        for ln, line in enumerate(plane.lines):
            if m:
                if line.name == reduce_trace.OPS_LINE:
                    chips[int(m.group(1))] = reduce_trace._events(line)
                continue
            for ev in line.events:
                if ev.name == WINDOW and window is None:
                    window = (float(ev.start_ns),
                              float(ev.start_ns + ev.duration_ns))
                    window_line = (p, ln)
                elif ev.name.startswith(SPAN_PREFIX):
                    lines.setdefault((p, ln), []).append((
                        ev.name[len(SPAN_PREFIX):], float(ev.start_ns),
                        float(ev.start_ns + ev.duration_ns),
                        {str(k): v for k, v in ev.stats},
                    ))
    if not lines or window is None:
        return {"idle": None, "compiles": None}
    lo, hi = window
    compiles = sum(1 for spans in lines.values() for sp in spans
                   if sp[0] == COMPILE and lo <= sp[1] <= hi) / passes
    idle = None
    if chips:
        ops = chips[min(chips)]
        mine = [sp for sp in lines.get(window_line, ())
                if sp[2] > lo and sp[1] < hi]
        gaps = reduce_trace.gaps([(a, b) for _, a, b in ops], lo, hi)
        idle = attribute(gaps, mine, passes)
    return {"idle": idle, "compiles": compiles}


def print_table(table: dict, out=sys.stderr) -> None:
    idle = table["idle"]
    if idle is not None:
        print(f"device idle by program span, s/pass (total "
              f"{idle['total_s']:.3f}):", file=out)
        print(f"  {'span':44s} {'group':13s} {'idle_s':>9s} {'span_s':>9s} "
              f"{'n':>4s} leaf", file=out)
        for label, row in sorted(idle["by_span"].items(),
                                 key=lambda kv: -kv[1]["idle_s"]):
            print(f"  {label:44s} {row['group']:13s} {row['idle_s']:9.3f} "
                  f"{row['span_s']:9.3f} {row['count']:4d} "
                  f"{'leaf' if row['leaf'] else ''}", file=out)
        for group in GROUPS:
            print(f"  group {group:38s} {'':13s} "
                  f"{idle['by_group'][group]:9.3f}", file=out)
    if table["compiles"] is not None:
        print(f"xla.compile markers in the window, per pass: "
              f"{table['compiles']:g}", file=out)
    out.flush()


def table_of(ctx: dict) -> dict:
    """The run's table, read once and kept on ``ctx`` for the next reader."""
    if "program_spans" not in ctx:
        trace = ctx.get("trace")
        if not trace or not ctx.get("passes"):
            ctx["program_spans"] = {"idle": None, "compiles": None}
        else:
            ctx["program_spans"] = read_trace(trace["path"],
                                              len(ctx["passes"]))
            print_table(ctx["program_spans"])
    return ctx["program_spans"]


def idle_seconds(ctx: dict, group: str):
    """Idle seconds per pass of ``group``; ``None`` (never 0) when the trace
    holds no ``sntc:`` span or no device plane."""
    idle = table_of(ctx)["idle"]
    return None if idle is None else idle["by_group"][group]


def compiles_in_window(ctx: dict):
    return table_of(ctx)["compiles"]


if __name__ == "__main__":
    print_table(read_trace(sys.argv[1],
                           int(sys.argv[2]) if len(sys.argv) > 2 else 1),
                out=sys.stdout)
