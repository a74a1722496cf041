"""Host seconds per out-of-core product in the combine phase (OotStats.combine_s)."""


def read(r):
    if not r.stats:
        return None
    return sum(s["combine_s"] for s in r.stats) / len(r.stats)
