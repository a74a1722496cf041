"""Host seconds per out-of-core product ingesting A and B into the block store.

OotStats.ingest_s, the duration of the program's ``oot.ingest`` span.
"""


def read(r):
    if not r.stats or any("ingest_s" not in s for s in r.stats):
        return None
    return sum(s["ingest_s"] for s in r.stats) / len(r.stats)
