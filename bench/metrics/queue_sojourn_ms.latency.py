"""Mean time a request spent in the queue, submit to delivery: the
change of the buckets' ``wait_s`` over the change of ``completed``
across the window (DetQueue counters, host clock)."""

from . import bucket_delta, delta


def read(run):
    if "buckets" not in run.counters1:
        return None
    done = delta(run, "completed")
    if done <= 0:
        return None
    return 1e3 * bucket_delta(run, "wait_s") / done
