"""Device ms per product in collectives (all-gather, all-reduce, ... with
their -start and -done halves), averaged over the cell's devices."""
from bench import trace


def read(r):
    if r.trace is None or not r.traced_products:
        return None
    per_dev = r.trace.class_seconds(trace.COLLECTIVE)
    return 1e3 * sum(per_dev) / len(per_dev) / r.traced_products
