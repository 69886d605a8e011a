"""idle_s.unattributed.fit: the device's idle seconds per fit under no program
span narrower than ``pipeline.fit``: ``Pipeline``'s own loop, the harness's
frame and pipeline building, and whatever the spans miss.  One of the four
that partition the traced window's idle time (``program_spans.py``); nothing
to read gives no number, never 0."""

import program_spans


def read(ctx):
    return program_spans.idle_seconds(ctx, "unattributed")
