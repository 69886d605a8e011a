"""grad_eval_ms.fit: device milliseconds of the estimator's fit program
(the adapter's ``PROGRAMS["fit"]``, its ``XLA Modules`` events in the
traced window) per fit, over the fit's loss-and-gradient evaluations as
``grad_evals.fit`` counts them.  The one forward pass for the loss at the
final weights is in the time and not in the count (a third of an
evaluation in a hundred).  Nothing to read (a trace without a device plane,
a program without the counter) gives no number, never 0."""

import first_call
import reduce_trace


def read(ctx):
    trace, adapter = ctx.get("trace"), ctx["adapter"]
    pattern = getattr(adapter, "PROGRAMS", {}).get("fit")
    rows = first_call.series("sntc_mlp_grad_evals_total")
    if not trace or not ctx.get("passes") or not pattern or not rows:
        return None
    evals = sum(v for _, v in rows) / (len(ctx["passes"]) + 1)
    seconds = reduce_trace.kernel_seconds(trace, pattern, by="module")
    if not seconds or not evals:
        return None
    return 1000.0 * seconds / len(ctx["passes"]) / evals
