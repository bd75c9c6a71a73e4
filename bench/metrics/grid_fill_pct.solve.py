"""Share of the windowed grid's rank slots that held a real rank in a
solve loop past int32: 100 × C(n, m) × the solves completed, over the
change of the engine's ``plan_cache.grid_ranks`` counter across the
window (DetEngine counter: W · steps · 8 · tile slots per call, D · W_d
windows over a mesh).  A cut that leaves a chip padded windows, or a
longer window than its neighbours, reads low here, where device time
cannot show it: every chip runs the same static grid.  None where the
engine has no such counter or no windowed grid ran."""

import math

from . import delta


def read(run):
    vals = run.rec.get("vals")
    if not vals or "grid_ranks" not in run.counters1.get("plan_cache", {}):
        return None
    slots = delta(run, "plan_cache", "grid_ranks")
    if slots <= 0:
        return None
    m, n = run.config["shape"]
    return 100.0 * math.comb(n, m) * len(vals) / slots
