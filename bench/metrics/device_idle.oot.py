"""Share of the traced window in which no op ran on the device, in %.

1 - (union of the device's op intervals / window), averaged over the cell's
devices.
"""


def read(r):
    if r.trace is None:
        return None
    return 100.0 * r.trace.idle_share()
