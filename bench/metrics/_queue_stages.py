"""Shared by the readers of DetQueue's per-stage counters: not a metric
of its own.  Where the program keeps no such counter, each returns
None, so that a run of a program without them leaves the metric out."""

from __future__ import annotations

from . import bucket_delta, delta


def segment_ms(run, field: str):
    """Mean milliseconds per completed request of one segment of the
    queue's wait (``queued_s``, ``staging_s``, ``inflight_s`` or
    ``delivery_s``): 1e3 × Δ Σ bucket ``field`` / Δ ``completed`` across
    the window (DetQueue counters, host clock)."""
    buckets = run.counters1.get("buckets")
    if not buckets or any(field not in b for b in buckets.values()):
        return None
    done = delta(run, "completed")
    if done <= 0:
        return None
    return 1e3 * bucket_delta(run, field) / done


def gc_pause_pct(run):
    """The process's garbage-collection pauses across the window, as a
    share of the window: 100 × Δ ``gc.pause_s`` / (t1 − t0)."""
    g0, g1 = run.counters0.get("gc"), run.counters1.get("gc")
    window = run.rec["t1"] - run.rec["t0"]
    if g0 is None or g1 is None or window <= 0:
        return None
    return 100.0 * (g1["pause_s"] - g0["pause_s"]) / window
