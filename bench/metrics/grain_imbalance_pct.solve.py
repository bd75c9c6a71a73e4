"""How unevenly the grains load the devices: (max − mean) / mean of the
value kernel's device time per device (device trace)."""

import numpy as np

from . import kernel_seconds, traced


def read(run):
    if not traced(run) or len(run.devices) < 2:
        return None
    t = np.array(kernel_seconds(run))
    if t.mean() <= 0:
        return None
    return 100.0 * float((t.max() - t.mean()) / t.mean())
