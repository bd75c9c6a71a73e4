"""The windowed value kernel's share of its roofline in a solve loop
past int32: the algorithmic work of the window's solves (bench/work.py)
at the chip's published peak, over the device time of the ops named
``radic_windowed_partial_pallas`` summed over every device (device
trace).  None where no such op ran, as in a program without rank
windows."""

import re

from bench import work

from . import kernel_seconds, traced

WINDOWED_KERNEL = re.compile(r"^radic_windowed_partial_pallas$")


def read(run):
    if not traced(run) or not run.rec.get("vals"):
        return None
    t_kernel = sum(kernel_seconds(run, WINDOWED_KERNEL))
    if t_kernel <= 0:
        return None
    m, n = run.config["shape"]
    solves = len(run.rec["vals"])
    t_min, _ = work.roofline_seconds(work.det_flops(m, n, solves),
                                     work.det_bytes(m, n, solves),
                                     run.device_kind)
    return 100.0 * t_min / t_kernel
