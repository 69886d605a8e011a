"""hist_psums.fit: the all-reduce operations chip 0 ran inside the traced
window, per fit.  A witness and no target, as ``boost_rounds.fit`` is: the
one-vs-rest boosted fit sums one histogram a tree level over the chips
(``maxIter`` rounds x ``maxDepth`` levels, every node of a level in one
group: 20 x 5 = 100), so every seed reads the same number, which says that
every seed does the same collective work and that no other program of the
fit holds an all-reduce.  A trace without a device plane, or a program on
one chip, gives no number, never 0."""

import re

import reduce_trace

#: one executed all-reduce: the synchronous form, or the start of a pair
ALL_REDUCE = re.compile(r"\ball-reduce(-start)?\(")


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx.get("passes"):
        return None
    from jax.profiler import ProfileData

    data = ProfileData.from_file(trace["path"])
    window = [s for s in reduce_trace.host_spans(data) if s[0] == "window"]
    chips = {}
    for plane in data.planes:
        m = reduce_trace.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == reduce_trace.OPS_LINE:
                    chips[int(m.group(1))] = reduce_trace._events(line)
    if not chips:
        return None
    _, lo, hi = window[0] if window else (None, float("-inf"), float("inf"))
    count = sum(1 for name, start, _ in chips[min(chips)]
                if lo <= start <= hi and ALL_REDUCE.search(name))
    return count / len(ctx["passes"]) if count else None
