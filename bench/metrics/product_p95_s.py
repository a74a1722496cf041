"""95th percentile of the window's product latencies (host clock)."""
import numpy as np


def read(r):
    return float(np.percentile(r.latencies, 95))
