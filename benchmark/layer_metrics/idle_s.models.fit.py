"""idle_s.models.fit: the device's idle seconds per fit whose innermost
program span carries ``module=models``: extraction, bin edges, bagging draws,
the forest's fetch.  One of the four that partition the traced window's idle
time (``program_spans.py``); nothing to read gives no number, never 0."""

import program_spans


def read(ctx):
    return program_spans.idle_seconds(ctx, "models")
