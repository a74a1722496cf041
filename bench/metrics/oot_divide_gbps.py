"""Rate of the out-of-core divide phase, in GB/s (1e9 bytes per second).

The bytes of the blocks the divide reads from the block store and writes to
it (OotStats.divide_bytes, an exact count) over the divide phase's host
seconds (OotStats.divide_s), summed over the window's products.
"""


def read(r):
    if not r.stats or any("divide_bytes" not in s for s in r.stats):
        return None
    seconds = sum(s["divide_s"] for s in r.stats)
    if seconds <= 0:
        return None
    return sum(s["divide_bytes"] for s in r.stats) / seconds / 1e9
