"""device_idle_share.fit: the share of the traced window in which no
operation ran on the device (1 - union of device-op intervals / window)."""

import work


def read(ctx):
    return work.idle_share(ctx)
