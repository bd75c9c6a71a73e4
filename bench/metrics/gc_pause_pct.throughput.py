"""Share of the window the process spent in garbage collection: 100 ×
Δ ``gc.pause_s`` / (t1 − t0), from DetQueue's ``gc.callbacks`` hook
(``snapshot()["gc"]``, host clock)."""

from ._queue_stages import gc_pause_pct


def read(run):
    return gc_pause_pct(run)
