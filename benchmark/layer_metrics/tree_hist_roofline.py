"""tree_hist_roofline: the least time the chip could take for the histograms
the fit had to build (one read of the bin ids per tree level, from shapes)
against the summed device time of the ``tree_hist`` kernel's events in the
trace (its custom calls are named after ``level_histogram_pallas``), per fit.  Nothing to read (the trace does not name the kernel, or the
XLA twin ran instead) gives no number, never 0."""

import reduce_trace
import work

KERNEL = r"level_histogram_pallas|tree_hist"


def read(ctx):
    trace = ctx.get("trace")
    adapter = ctx["adapter"]
    if not trace or not ctx["passes"] or not hasattr(adapter, "work_tree_hist"):
        return None
    ran = any(
        'kernel="tree_hist"' in k and 'impl="pallas"' in k and v > 0
        for k, v in ctx.get("counters", {}).items()
    )
    seconds = reduce_trace.kernel_seconds(trace, KERNEL)
    if not ran or not seconds:
        return None
    need = adapter.work_tree_hist(ctx["cfg"], ctx["rows"])
    per_fit = seconds / len(ctx["passes"])
    return work.share_percent(need, per_fit, ctx["peaks"], ctx["cell"]["chips"])
