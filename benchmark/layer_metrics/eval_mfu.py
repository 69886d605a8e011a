"""eval_mfu: the work one forward pass of the head needs (from shapes,
counted by the estimator's adapter) against the chip's peak, over the traced
device time of the head's own program (the adapter's
``PROGRAMS["evaluate"]``)."""

import work


def read(ctx):
    return work.program_share(ctx, "evaluate")
