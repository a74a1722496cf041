"""Host seconds per out-of-core product assembling C.

OotStats.assemble_s, the duration of the program's ``oot.assemble`` span:
the C root to one dense array, cropped and cast, and the roots freed.
"""


def read(r):
    if not r.stats or any("assemble_s" not in s for s in r.stats):
        return None
    return sum(s["assemble_s"] for s in r.stats) / len(r.stats)
