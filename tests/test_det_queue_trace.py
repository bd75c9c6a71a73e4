"""DetQueue's own spans and per-stage counters.

Per bucket, a request's wait splits into four stamped segments —
``queued_s`` (submit → round start), ``staging_s`` (round start →
dispatch), ``inflight_s`` (dispatch → device ready) and ``delivery_s``
(ready → delivered) — that add up to ``wait_s``.  ``snapshot()["gc"]``
counts the process's garbage collections.  Under a profiler, the
pipeline's spans land on the host plane by their bare names.
"""

import gc

import jax
import numpy as np
import pytest

from repro.core import DetEngine
from repro.launch.det_queue import BucketPolicy, DetQueue

SEGMENTS = ("queued_s", "staging_s", "inflight_s", "delivery_s")


def _serve(q, rng, shapes, grad=False, per_shape=6):
    mats = [rng.normal(size=s).astype(np.float32)
            for s in shapes for _ in range(per_shape)]
    futs = q.submit_many(mats, [(grad, 1.0)] * len(mats))
    for f in futs:
        f.result(timeout=120)
    return q.snapshot()


@pytest.mark.parametrize("case", ["value", "gradient", "merged", "m_gt_n"])
def test_wait_segments_add_up_per_bucket(rng, case):
    shapes = {"value": [(3, 7)], "gradient": [(2, 6)],
              "merged": [(2, 5), (2, 6)], "m_gt_n": [(4, 2)]}[case]
    mode = "merge" if case == "merged" else "never"
    with DetQueue(policy=BucketPolicy(max_batch=4, mode=mode)) as q:
        stats = _serve(q, rng, shapes, grad=case == "gradient")
    assert stats["completed"] == 6 * len(shapes)
    if case == "merged":
        assert stats["merged_requests"] > 0
    assert stats["buckets"]
    for b in stats["buckets"].values():
        parts = [b[k] for k in SEGMENTS]
        assert all(p >= 0.0 for p in parts), b
        # wait_s is reckoned on its own from the stamps: equal to rounding
        assert sum(parts) == pytest.approx(b["wait_s"], rel=1e-9, abs=1e-6)
        if case == "m_gt_n":   # no device work: all of it is staging
            assert b["inflight_s"] == b["delivery_s"] == 0.0
            assert b["staging_s"] > 0.0
        else:
            assert b["inflight_s"] > 0.0 and b["delivery_s"] > 0.0


def test_gc_counter_counts_collections():
    with DetQueue() as q:
        before = q.snapshot()["gc"]
        gc.collect()
        after = q.snapshot()["gc"]
    assert after["collections"] > before["collections"]
    assert after["pause_s"] > before["pause_s"]


def test_profiler_trace_holds_the_pipeline_spans(tmp_path, rng):
    """A CPU trace read the way the benchmark reads one: every span by
    its bare name, though the per-batch spans carry the round number as
    metadata."""
    from bench.trace import find_span, load
    jax.profiler.start_trace(str(tmp_path))
    try:
        # a fresh engine, so the first batch builds its plan in the trace
        with DetQueue(engine=DetEngine(),
                      policy=BucketPolicy(max_batch=4, mode="never")) as q:
            _serve(q, rng, [(2, 5)])
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    planes = load(str(tmp_path))
    for name in ("det.stage", "det.stage.batch", "det.dispatch",
                 "det.complete.wait", "det.complete.deliver",
                 "det.plan.build", "gc.collect"):
        start, end = find_span(planes, name)
        assert end >= start
