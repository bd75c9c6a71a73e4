"""Mean time from the start of a request's stager round to its batch's
dispatch: pad, stack, upload, plan lookup and the executable call
(all of an m > n batch's stay, which needs no device):
Δ Σ bucket ``staging_s`` / Δ ``completed`` across the window (DetQueue
counters, host clock)."""

from ._queue_stages import segment_ms


def read(run):
    return segment_ms(run, "staging_s")
