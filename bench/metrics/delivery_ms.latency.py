"""Mean time from the device's finish of a request's batch to its
delivery: readback, responses, counters and the futures resolving:
Δ Σ bucket ``delivery_s`` / Δ ``completed`` across the window (DetQueue
counters, host clock)."""

from ._queue_stages import segment_ms


def read(run):
    return segment_ms(run, "delivery_s")
