"""collective_share.fit: the share of the chips' busy time that goes to the
fit's collectives: the device seconds of the all-reduce operations in the
traced window (an operation's own event, start to done, so a chip's wait for
the slowest of its peers is in it), the mean over the chips the cell uses,
over the traced busy seconds (the same mean).  The boosted and the forest
fits hold one collective, the per-level ``psum`` of the ``tree_hist``
histograms over the mesh's row axis (``models/tree/grower.py:_group_hist``).
A trace without a device plane, or a program on one chip (XLA drops an
all-reduce over one device), gives no number, never 0."""

import reduce_trace

#: the all-reduce's HLO text in the ``XLA Ops`` line, the synchronous form
#: and the asynchronous pair
ALL_REDUCE = r"\ball-reduce(-start|-done)?\("


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = reduce_trace.kernel_seconds(trace, ALL_REDUCE)
    if seconds is None:
        return None
    return 100.0 * seconds / trace["busy_s"]
