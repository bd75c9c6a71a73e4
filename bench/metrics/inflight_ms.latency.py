"""Mean time from a request's batch's dispatch until the device had
finished the batch:
Δ Σ bucket ``inflight_s`` / Δ ``completed`` across the window (DetQueue
counters, host clock)."""

from ._queue_stages import segment_ms


def read(run):
    return segment_ms(run, "inflight_s")
