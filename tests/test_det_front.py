"""Battery for the multi-worker bucket-routing serving front.

The load-bearing invariant mirrors the DetQueue battery one level up:
per-request results are independent of *which worker* served them and
of any re-routing that happened along the way.  With capacity pinned
and the merge policy fixed, a request's determinant through a 2-worker
``DetFront`` is bit-identical to the single-process ``DetQueue`` — and
stays bit-identical when the owning worker is SIGKILLed mid-flight and
its pending requests re-plan on the survivor (plans are pure functions
of their key).

Worker processes spawn real jax-importing children; the module keeps
the request counts small and shares policies so the battery stays
CI-sized.
"""

import numpy as np
import pytest

from repro.core import comb
from repro.core.engine import stable_key_hash
from repro.launch.det_front import (DetFront, HashRing, WorkerError,
                                    route_key)
from repro.launch.det_queue import (BucketPolicy, DetQueue, LoadShedError,
                                    QueueClosedError)

CHUNK = 128
CAP = 8
# the DetQueue battery's heterogeneous pool, incl. one m > n degenerate
SHAPES = [(1, 4), (2, 5), (2, 6), (3, 7), (3, 9), (4, 10), (4, 2)]

PINNED = BucketPolicy(max_batch=CAP, mode="merge", pin_capacity=True)


def _mats(rng, num):
    out = []
    for _ in range(num):
        m, n = SHAPES[int(rng.integers(0, len(SHAPES)))]
        out.append(rng.normal(size=(m, n)).astype(np.float32))
    return out


def _queue_reference(mats, policy=PINNED):
    """The single-process ground truth for a request set."""
    with DetQueue(chunk=CHUNK, policy=policy) as q:
        dets, _ = q.serve(mats, timeout=300)
    return dets


# --------------------------------------------------------------- pure pieces
def test_stable_key_hash_is_process_stable():
    """The ring hash must not depend on PYTHONHASHSEED — pin a value so
    any accidental fallback to builtin hash() fails loudly."""
    key = (3, 9, 8, "float32", False)
    assert stable_key_hash(key) == stable_key_hash(tuple(key))
    assert stable_key_hash(key) != stable_key_hash((3, 9, 8, "float64",
                                                    False))
    import pathlib
    import subprocess
    import sys
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.core.engine import stable_key_hash;"
         "print(stable_key_hash((3, 9, 8, 'float32', False)))"],
        capture_output=True, text=True,
        env={"PYTHONPATH": src, "PYTHONHASHSEED": "12345"})
    assert out.returncode == 0, out.stderr
    assert int(out.stdout) == stable_key_hash(key)


def test_route_key_projects_policy_canonical_shape():
    merge = BucketPolicy(max_batch=8, mode="merge", col_class=4, col_max=16)
    never = BucketPolicy(max_batch=8, mode="never")
    # merging policies route by canonical shape: everything that could
    # coalesce must share one owner
    assert route_key((2, 5), merge, np.float32, False) \
        == route_key((2, 6), merge, np.float32, False) \
        == (2, 8, 8, "float32", False)
    # exact-shape policies route exact
    assert route_key((2, 5), never, np.float32, False) \
        != route_key((2, 6), never, np.float32, False)
    # dtype and x64 select different program families
    assert route_key((2, 5), never, np.float32, False) \
        != route_key((2, 5), never, np.float64, False)
    assert route_key((2, 5), never, np.float32, False) \
        != route_key((2, 5), never, np.float32, True)


def test_hash_ring_consistency_on_removal():
    """Removing one worker moves only the keys it owned; every other
    key keeps its owner — the consistent-hashing property that makes
    re-routing deterministic and minimal."""
    ring = HashRing([0, 1, 2], vnodes=64)
    keys = [(m, n, 8, "float32", False) for m in range(1, 6)
            for n in range(m, 12)]
    before = {k: ring.owner(k) for k in keys}
    ring.remove(1)
    after = {k: ring.owner(k) for k in keys}
    for k in keys:
        if before[k] != 1:
            assert after[k] == before[k]
        else:
            assert after[k] != 1
    # walk order: first element is the owner, all workers appear once
    ring2 = HashRing([0, 1, 2], vnodes=64)
    for k in keys:
        w = ring2.walk(k)
        assert w[0] == ring2.owner(k) and sorted(w) == [0, 1, 2]


def test_hash_ring_empty_and_validation():
    with pytest.raises(RuntimeError):
        HashRing([]).owner((1, 2, 3))
    with pytest.raises(ValueError):
        HashRing([0], vnodes=0)
    assert HashRing([]).walk((1, 2, 3)) == []


# ------------------------------------------------------------- bit identity
@pytest.mark.parametrize("workers", [1, 2])
def test_front_bit_identical_to_single_queue(workers, rng):
    """The tentpole invariant: the same request set produces identical
    bits through DetQueue (1 process) and DetFront (1 and 2 workers)."""
    mats = _mats(rng, 30)
    want = _queue_reference(mats)
    with DetFront(workers=workers, chunk=CHUNK, policy=PINNED) as front:
        got, stats = front.serve(mats, timeout=300)
        assert front.alive_workers == list(range(workers))
    assert got == want
    assert stats["front"]["submitted"] == 30
    assert stats["total"]["completed"] == 30
    assert stats["front"]["worker_deaths"] == 0


@pytest.mark.parametrize("workers,shm", [(1, False), (2, False), (2, True)])
def test_front_grad_bit_identical_to_single_queue(workers, shm, rng):
    """Gradient traffic extends the tentpole invariant (DESIGN_GRAD.md):
    a mixed value/grad burst with nonuniform cotangents produces
    bit-identical results through DetFront — local and zero-copy shm
    transports — as through the 1-process DetQueue.  Grad results are
    (m, n) arrays; every bit must survive the wire."""
    mats = _mats(rng, 20)
    grads = [(i % 3 == 0, [1.0, -2.0, 0.5, 1.5][i % 4])
             for i in range(len(mats))]
    with DetQueue(chunk=CHUNK, policy=PINNED) as q:
        want = [f.result(timeout=300)
                for f in q.submit_many(mats, grads)]
    with DetFront(workers=workers, chunk=CHUNK, policy=PINNED,
                  shm=shm) as front:
        got = [f.result(timeout=300)
               for f in front.submit_many(mats, grads)]
    for i, (g, w) in enumerate(zip(got, want)):
        if grads[i][0]:
            assert isinstance(g, np.ndarray)
            assert g.shape == mats[i].shape
            np.testing.assert_array_equal(g, w)  # bit identity, no tol
        else:
            assert g == w


def test_front_worker_kill_reroutes_bit_identical(rng):
    """SIGKILL the worker that owns a hot shape while its requests are
    pending: the front must detect the death, re-route the orphans to
    the survivor, and still deliver bit-identical results for every
    request (plans are pure functions of the key)."""
    mats = _mats(rng, 40)
    want = _queue_reference(mats)
    with DetFront(workers=2, chunk=CHUNK, policy=PINNED) as front:
        victim = front.owner_of((3, 9))
        futs = front.submit_many(mats)
        front.kill_worker(victim)
        got = [f.result(timeout=300) for f in futs]
        stats = front.snapshot()
        assert front.alive_workers == [1 - victim]
    assert got == want
    assert stats["front"]["worker_deaths"] == 1
    # the kill landed before the first result could possibly complete
    # (cold compile takes far longer than the submit->kill window), so
    # the victim's routed share was actually re-routed
    assert stats["front"]["rerouted"] > 0
    # the front delivered every request exactly once (the dead worker's
    # own counters died with it; the front's view is authoritative)
    assert stats["front"]["completed"] == 40


def test_front_retire_worker_drains_and_requeues(rng):
    """The graceful-downscale path: retire_worker hands the un-staged
    backlog back via DetQueue.drain_pending, the ring drops the worker,
    and everything still resolves bit-identically on the survivor."""
    mats = [rng.normal(size=(3, 7)).astype(np.float32)
            for _ in range(CAP - 2)]
    want = _queue_reference(mats)
    # fewer requests than a batch, and linger keeps that partial batch
    # un-staged long enough for the retire to deterministically catch
    # it in drain_pending (a full batch would stage at once: workers are
    # up, their device open, before the front takes a request)
    with DetFront(workers=2, chunk=CHUNK, policy=PINNED,
                  linger_s=3.0) as front:
        victim = front.owner_of((3, 7))
        futs = front.submit_many(mats)
        front.retire_worker(victim)
        got = [f.result(timeout=300) for f in futs]
        stats = front.snapshot()
        assert front.alive_workers == [1 - victim]
    assert got == want
    assert stats["front"]["worker_deaths"] == 0  # clean exit, not a death
    assert stats["front"]["rerouted"] > 0


def test_front_all_workers_dead_fails_pending(rng):
    mats = [rng.normal(size=(3, 9)).astype(np.float32) for _ in range(8)]
    front = DetFront(workers=1, chunk=CHUNK, policy=PINNED)
    try:
        futs = front.submit_many(mats)
        front.kill_worker(0)
        for f in futs:
            with pytest.raises(RuntimeError):
                f.result(timeout=120)
        with pytest.raises(RuntimeError):
            front.submit(mats[0])
    finally:
        front.close()


# ------------------------------------------------ ownership and balance
def test_plan_ownership_is_exclusive_and_sticky(rng):
    """Every shape's plan family lives on exactly one worker: the
    aggregated pool plan-cache misses equal the number of distinct
    program families — no duplicated XLA compiles across the pool."""
    shapes = [(2, 5), (3, 7), (3, 9), (4, 10)]
    mats = [rng.normal(size=shapes[i % 4]).astype(np.float32)
            for i in range(32)]
    with DetFront(workers=2, chunk=CHUNK, policy=PINNED) as front:
        owners = {s: front.owner_of(s) for s in shapes}
        front.serve(mats, timeout=300)
        stats = front.snapshot()
        assert {s: front.owner_of(s) for s in shapes} == owners  # sticky
    # merge policy canonicalizes (2,5)->(2,8), (3,7)/(3,9)->(3,12)...
    families = {route_key(s, PINNED, np.float32, False) for s in shapes}
    assert stats["total"]["plan_cache"]["misses"] == len(families)
    per_worker_sizes = [snap["plan_cache"]["size"]
                        for snap in stats["workers"].values()]
    assert sum(per_worker_sizes) == len(families)


def test_bounded_load_placement_splits_equal_families():
    """With K equal-weight plan families and N workers, bounded-load
    placement may not park more than (1 + eps) * K/N weight on any one
    worker — the raw-arc split that motivated it routinely does."""
    with DetFront(workers=2, chunk=CHUNK,
                  policy=BucketPolicy(max_batch=CAP, mode="never")) as front:
        shapes = [(3, n) for n in range(8, 24)]  # 16 families
        for s in shapes:
            front.owner_of(s)
        loads = front.snapshot(timeout=60)["front"]["plan_load"]
    total = sum(loads.values())
    assert len(loads) == 2 and total > 0
    assert max(loads.values()) <= total * (1 + front._balance_eps) / 2 \
        + max(comb(n, 3) for _, n in shapes)


# ------------------------------------------------------ queue-surface parity
def test_front_loadshed_propagates_end_to_end(rng):
    """Per-worker admission control must surface as LoadShedError on the
    front's futures AND its poll stream, exactly once per request."""
    A = rng.normal(size=(2, 5)).astype(np.float32)
    with DetFront(workers=2, chunk=CHUNK, max_pending=2,
                  policy=BucketPolicy(max_batch=CAP,
                                      pin_capacity=True)) as front:
        futs = front.submit_many([A] * 10)  # one shape -> one worker
        excs = [f.exception(timeout=120) for f in futs]
        served = [f for f, e in zip(futs, excs) if e is None]
        shed = [f for f, e in zip(futs, excs)
                if isinstance(e, LoadShedError)]
        assert len(served) == 2 and len(shed) == 8
        by_seq = {}
        while len(by_seq) < 10:
            got = front.poll(timeout=60.0)
            assert got, "poll timed out with responses outstanding"
            by_seq.update(got)
        stats = front.snapshot()
    assert set(by_seq) == {f.seq for f in futs}
    assert sum(isinstance(v, LoadShedError) for v in by_seq.values()) == 8
    assert stats["front"]["shed"] == 8 and stats["total"]["shed"] == 8


def test_front_error_propagates_with_type(rng):
    """A worker-side plan-time failure (C(40,16) overflowing int32)
    surfaces as the same exception type on the front future; the pool
    keeps serving other requests."""
    with DetFront(workers=2, chunk=CHUNK, policy=PINNED) as front:
        bad = front.submit(np.ones((16, 40), np.float32))
        with pytest.raises(OverflowError):
            bad.result(timeout=300)
        ok = front.submit(np.ones((4, 2), np.float32))  # m > n => 0
        assert ok.result(timeout=300) == 0.0
        stats = front.snapshot()
    assert stats["front"]["errors"] == 1


def test_worker_error_rebuild_fallback():
    from repro.launch.det_front import _rebuild_exc
    assert isinstance(_rebuild_exc("OverflowError", "x"), OverflowError)
    assert isinstance(_rebuild_exc("LoadShedError", "x"), LoadShedError)
    exc = _rebuild_exc("SomeExoticError", "boom")
    assert isinstance(exc, WorkerError) and "SomeExoticError" in str(exc)


def test_front_poll_stream_exactly_once(rng):
    mats = _mats(rng, 20)
    with DetFront(workers=2, chunk=CHUNK, policy=PINNED) as front:
        futs = front.submit_many(mats)
        by_seq = {}
        while len(by_seq) < len(mats):
            got = front.poll(timeout=60.0)
            assert got, "poll timed out with responses outstanding"
            by_seq.update(got)
    assert by_seq == {f.seq: f.result() for f in futs}


def test_front_close_idempotent_and_rejects_submits(rng):
    front = DetFront(workers=1, chunk=CHUNK, policy=PINNED)
    fut = front.submit(rng.normal(size=(2, 5)).astype(np.float32))
    front.close()
    assert fut.done()  # close drains accepted work before stopping
    with pytest.raises(QueueClosedError):
        front.submit(np.ones((2, 5), np.float32))
    front.close()  # idempotent
    # the request's response is still pollable after close, then the
    # stream ends cleanly (no hang) even with timeout=None semantics
    assert front.poll(timeout=0.0) == [(fut.seq, fut.result())]
    assert front.poll(timeout=0.0) == []


def test_front_validation():
    with pytest.raises(ValueError):
        DetFront(workers=0)
    with pytest.raises(ValueError):
        DetFront(workers=1, max_batch=8,
                 policy=BucketPolicy(max_batch=64))


def test_front_stats_aggregation_shape(rng):
    mats = _mats(rng, 12)
    with DetFront(workers=2, chunk=CHUNK, policy=PINNED) as front:
        front.serve(mats, timeout=300)
        stats = front.snapshot()
    f, tot, per = stats["front"], stats["total"], stats["workers"]
    assert f["submitted"] == 12 and sum(f["routed"].values()) == 12
    assert tot["submitted"] == tot["completed"] == 12
    assert set(per) <= {0, 1} and len(per) == f["workers_alive"] == 2
    assert tot["backlog_peak"] == max(s["backlog_peak"]
                                      for s in per.values())
    for key in ("hits", "misses", "evictions", "size",
                "store_hits", "store_misses"):
        assert tot["plan_cache"][key] == sum(s["plan_cache"][key]
                                             for s in per.values())
    # no store configured: the store counters stay zero
    assert tot["plan_cache"]["store_hits"] == 0
    assert f["prefill"] is False and f["cold_workers"] == []
    # bucket merge across workers preserves counts
    assert sum(b["count"] for b in tot["buckets"].values()) == 12


# ------------------------------------------------------------ warm start
def test_front_warm_start_from_plan_store_bit_identical(rng, tmp_path):
    """The PR's end-to-end invariant (DESIGN_PERSIST.md): a front over a
    populated plan store restores plans instead of compiling (store hits
    in the aggregated snapshot) and every result stays bit-identical to
    the cold 1-process DetQueue."""
    mats = _mats(rng, 20)
    want = _queue_reference(mats)  # cold reference, no store anywhere
    store = str(tmp_path / "plans")
    # populate the store: one cold pass through a persistent DetQueue
    with DetQueue(chunk=CHUNK, policy=PINNED, persist_dir=store) as q:
        q.serve(mats, timeout=300)
    with DetFront(workers=1, chunk=CHUNK, policy=PINNED,
                  persist_dir=store) as front:
        got, stats = front.serve(mats, timeout=300)
    assert got == want
    pc = stats["total"]["plan_cache"]
    assert pc["store_hits"] >= 1        # the worker arrived warm
    assert stats["front"]["prefill"] is True  # auto-on with a store


def test_front_join_with_prefill_warms_before_admission(rng, tmp_path):
    """A worker joining via the accept listener with a populated plan
    store is shipped the front's live plan families in the handshake and
    warms them (store first) before it is admitted: its very first
    snapshot shows store hits, and results match the cold join exactly."""
    import threading
    from repro.launch.transport import run_worker_client
    mats = _mats(rng, 24)
    want = _queue_reference(mats)
    store = str(tmp_path / "plans")
    with DetQueue(chunk=CHUNK, policy=PINNED, persist_dir=store) as q:
        q.serve(mats, timeout=300)
    with DetFront(workers=1, chunk=CHUNK, policy=PINNED,
                  persist_dir=store, accept="127.0.0.1:0") as front:
        first = [f.result(timeout=300)
                 for f in front.submit_many(mats[:12])]
        assert front._prefill_entries()  # live families to ship
        joiner = threading.Thread(
            target=run_worker_client, args=(front.accept_address,),
            kwargs={"log": lambda *a, **k: None}, daemon=True)
        joiner.start()
        deadline = 60.0
        import time
        t0 = time.monotonic()
        while len(front.alive_workers) != 2:
            assert time.monotonic() - t0 < deadline
            time.sleep(0.05)
        snap = front.snapshot()
        joiner_wid = [w for w in front.alive_workers if w != 0][0]
        jpc = snap["workers"][joiner_wid]["plan_cache"]
        # admitted already warm: the prefill consulted the store before
        # the worker answered ready
        assert jpc["store_hits"] >= 1
        assert jpc["size"] >= 1
        rest = [f.result(timeout=300)
                for f in front.submit_many(mats[12:])]
    joiner.join(timeout=30)
    assert first + rest == want


@pytest.mark.parametrize("shm", [False, True])
def test_worker_without_its_device_fails_start(monkeypatch, shm):
    """A chip belongs to one process: a worker that cannot open its
    device (here: a platform this host does not have) fails the pool's
    start-up with its own error, and is never re-routed around."""
    from repro.launch.transport import TransportError
    monkeypatch.setenv("JAX_PLATFORMS", "nonexistent")  # spawned workers
    with pytest.raises(TransportError,
                       match="could not open its device.*nonexistent"):
        DetFront(workers=2, chunk=CHUNK, policy=PINNED, shm=shm)
