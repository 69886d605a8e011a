"""boost_walk_roofline: the least time the chip could take for the boosted
fit's margin updates (the adapter's ``work_walk``: a round reads the row's
features once and reads and writes its class margins) against the traced
device seconds of the walk's own program (the adapter's ``PROGRAMS["walk"]``)
per fit.  Nothing to read (an adapter without a walk, a trace that holds no
such program) gives no number, never 0."""

import reduce_trace
import work


def read(ctx):
    trace, adapter = ctx.get("trace"), ctx["adapter"]
    pattern = getattr(adapter, "PROGRAMS", {}).get("walk")
    if (not trace or not ctx["passes"] or not pattern
            or not hasattr(adapter, "work_walk")):
        return None
    seconds = reduce_trace.kernel_seconds(trace, pattern, by="module")
    if not seconds:
        return None
    need = adapter.work_walk(ctx["cfg"], ctx["rows"])
    return work.share_percent(need, seconds / len(ctx["passes"]),
                              ctx["peaks"], ctx["cell"]["chips"])
