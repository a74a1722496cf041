"""Host seconds per out-of-core product in the divide phase (OotStats.divide_s)."""


def read(r):
    if not r.stats:
        return None
    return sum(s["divide_s"] for s in r.stats) / len(r.stats)
