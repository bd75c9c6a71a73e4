"""How late the load generator ran: the 99th percentile, nearest rank,
of send time − due time over the window's requests (host clock)."""

import numpy as np


def read(run):
    if "sent" not in run.rec:
        return None
    lag = run.rec["sent"] - run.rec["due"]
    return 1e3 * float(np.quantile(lag, 0.99, method="inverted_cdf"))
