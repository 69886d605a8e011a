"""idle_s.upload.fit: the device's idle seconds per fit inside the placement
layer's ``h2d.*`` spans (``parallel/collectives.py``: ``h2d.put`` in
``_put_sharded``, ``h2d.pad`` before it).  One of the four that partition the
traced window's idle time (``program_spans.py``); nothing to read gives no
number, never 0."""

import program_spans


def read(ctx):
    return program_spans.idle_seconds(ctx, "upload")
