"""99th percentile, nearest rank, of the sojourn of every request due in
the window: from its due time to its future resolving (host clock).  A
request that failed or never came counts as beyond any limit."""

import numpy as np


def read(run):
    rec = run.rec
    if "due" not in rec:
        return None
    soj = rec["done"] - rec["due"]
    failed = np.array([r is None for r in rec["results"]])
    soj = np.where(np.isnan(soj) | failed, np.inf, soj)
    p99 = float(np.quantile(soj, 0.99, method="inverted_cdf"))
    return p99 * 1e3 if np.isfinite(p99) else 1e12
