"""fit_mfu: the work the estimator's fit needs (operations and bytes from
shapes, counted by the estimator's adapter) against the chip's peak, over the
traced device time of the estimator's own programs (the adapter's
``PROGRAMS["fit"]``): the larger of flops / peak flops and bytes / peak
bytes/s.  Host time is not in it: that is ``device_idle_share.fit``'s."""

import work


def read(ctx):
    return work.program_share(ctx, "fit")
