"""grad_evals.fit: loss-and-gradient evaluations a fit, by the program's own
count (``sntc_mlp_grad_evals_total``, ``models/mlp.py``: a gd fit adds its
steps when its optimiser returns), over the fits the harness's process made:
the warm-up pass and the traced window's passes.  A witness and no target,
as ``boost_rounds.fit`` is: a gd fit whose tolerance stop does not fire
reads ``maxIter`` on every seed, which says that every seed does the same
work.  A program without the counter gives no number, never 0."""

import first_call


def read(ctx):
    rows = first_call.series("sntc_mlp_grad_evals_total")
    if not rows or not ctx.get("passes"):
        return None
    return sum(v for _, v in rows) / (len(ctx["passes"]) + 1)
