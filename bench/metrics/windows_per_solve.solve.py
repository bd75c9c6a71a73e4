"""Rank windows dispatched per solve: the change of the engine's
``plan_cache.windows`` counter across the window, over the solves
completed (DetEngine counter).  It reads the plan's window count W, and
0 where the solves took another route.  None where the engine has no
such counter."""

from . import delta


def read(run):
    vals = run.rec.get("vals")
    if not vals or "windows" not in run.counters1.get("plan_cache", {}):
        return None
    return delta(run, "plan_cache", "windows") / len(vals)
