"""Requests completed inside the window over the window's length (host
clock)."""

import numpy as np


def read(run):
    rec = run.rec
    if "due" in rec or "done" not in rec:
        return None
    ok = np.array([r is not None for r in rec["results"]])
    served = int(np.sum(ok & (rec["done"] <= rec["t1"])))
    return served / (rec["t1"] - rec["t0"])
