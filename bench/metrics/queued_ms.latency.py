"""Mean time a request waited in the queue, from submit to the start of
the stager round that took it:
Δ Σ bucket ``queued_s`` / Δ ``completed`` across the window (DetQueue
counters, host clock)."""

from ._queue_stages import segment_ms


def read(run):
    return segment_ms(run, "queued_s")
