"""Share of the out-of-core leaf-wave phase in which the device runs no op, in %.

The program marks each product's leaf waves with an ``oot.leaf_waves`` host
event on the profiler's clock. Over the union of those events inside the
traced window: 1 - (device 0's op time inside them / their length).
"""
from bench import trace


def read(r):
    if r.trace is None:
        return None
    t0, t1 = r.trace.window
    waves = trace.union([(max(s, t0), min(e, t1)) for name, s, e in r.trace.host_events
                         if name == "oot.leaf_waves" and e > t0 and s < t1])
    total = sum(e - s for s, e in waves)
    if total <= 0:
        return None
    busy = sum(max(0.0, min(e, we) - max(s, ws))
               for ws, we in waves for s, e in r.trace.devices[0].busy)
    return 100.0 * (1.0 - busy / total)
