"""The fused value kernel's share of its roofline while serving values:
the algorithmic work of the real (not padded) matrices the queue
completed in the window, per shape (bench/work.py), at the chip's
published peak, over the kernel's device time (device trace).  Read
only where every request asks for a value."""

from bench import work

from . import kernel_seconds, traced


def read(run):
    if not traced(run) or run.traffic.get("grad_frac", 0.0) > 0:
        return None
    b0 = run.counters0.get("buckets", {})
    flops = nbytes = 0.0
    for (m, n), v in run.counters1.get("buckets", {}).items():
        k = v["count"] - b0.get((m, n), {}).get("count", 0)
        flops += work.det_flops(m, n, k)
        nbytes += work.det_bytes(m, n, k)
    t_kernel = sum(kernel_seconds(run))
    if flops <= 0 or t_kernel <= 0:
        return None
    t_min, _ = work.roofline_seconds(flops, nbytes, run.device_kind)
    return 100.0 * t_min / t_kernel
