"""Share of its roofline that the fused Strassen leaf kernel reaches, in %.

The least time is the larger of the kernel call's FLOPs over the peak and
its bytes over HBM bandwidth, counted by ``bench.work`` from each device's
own shapes (rows split evenly over the cell's devices) and the route's
depth; the kernel time is the summed device time of the Pallas kernel's
events per product. Averaged over devices.
"""
from bench import trace, work


def read(r):
    if r.trace is None or not r.traced_products:
        return None
    c, route = r.cell.config, r.cell.traffic["route"]
    w = work.fused_leaf_kernel(c["m"] // r.devices, c["k"], c["n"], route["depth"], c["dtype"])
    least = work.least_seconds(w, work.load_peaks(r.device_kind))
    per_dev = r.trace.class_seconds(trace.LEAF_KERNEL)
    if not all(per_dev):
        return None
    return sum(100.0 * least * r.traced_products / s for s in per_dev) / len(per_dev)
