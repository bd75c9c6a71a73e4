"""Share of the traced window that collective operations (all-reduce
and kin) took on a device, averaged over the devices (device trace)."""

import numpy as np

from . import traced


def read(run):
    if not traced(run) or len(run.devices) < 2:
        return None
    coll = [d["collective_s"] for d in run.devices]
    if not any(coll):
        return None
    return 100.0 * float(np.mean(coll)) / run.window_s
