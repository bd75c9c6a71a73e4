"""Plans built inside the window: the change of the engine's plan-cache
misses across it (DetEngine counter).  Set-up warms every program the
window uses, so this reads 0 unless a plan is built in the window."""

from . import delta


def read(run):
    if "plan_cache" not in run.counters1:
        return None
    return delta(run, "plan_cache", "misses")
