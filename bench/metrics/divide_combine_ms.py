"""Device ms per product in ops that are neither a leaf multiply nor a collective.

That is the Strassen divide and combine passes (copies, reshapes,
elementwise fusions), averaged over the cell's devices.
"""
from bench import trace


def read(r):
    if r.trace is None or not r.traced_products:
        return None
    per_dev = r.trace.class_seconds(trace.OTHER)
    return 1e3 * sum(per_dev) / len(per_dev) / r.traced_products
