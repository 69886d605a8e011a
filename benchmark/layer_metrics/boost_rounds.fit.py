"""boost_rounds.fit: the program's ``gbt.round`` spans inside the traced
window, per fit.  A witness and no target: a boosted fit of ``maxIter``
rounds reads ``maxIter`` on every seed, which says that every seed does the
same work (no early stop, no tolerance).  A trace without the span (a program
from before it existed) or without a device plane gives no number, never 0."""

import program_spans


def read(ctx):
    idle = program_spans.table_of(ctx)["idle"]
    row = None if idle is None else idle["by_span"].get("gbt.round")
    return None if row is None else row["count"] / len(ctx["passes"])
