"""idle_s.feature.fit: the device's idle seconds per fit whose innermost
program span carries ``module=feature``: the indexer, the assembler and the
selector's host work.  One of the four that partition the traced window's
idle time (``program_spans.py``); nothing to read gives no number, never 0."""

import program_spans


def read(ctx):
    return program_spans.idle_seconds(ctx, "feature")
