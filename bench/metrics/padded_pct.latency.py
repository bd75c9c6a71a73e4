"""Share of batch slots that held padding, not a request:
padded_slots / (padded_slots + completed) across the window (DetQueue
counters)."""

from . import delta


def read(run):
    if "padded_slots" not in run.counters1:
        return None
    pad, done = delta(run, "padded_slots"), delta(run, "completed")
    if pad + done <= 0:
        return None
    return 100.0 * pad / (pad + done)
