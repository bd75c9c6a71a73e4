"""The generator repeats exactly from a seed, gives every seed the same
work, and the open loop times requests from their due time."""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from bench import loops, traffic as gen
from bench.run import metric_reader

MIX = {"loop": "open", "rate_per_s": 500, "grad_frac": 0.25,
       "shapes": {"family": "head", "max_m": 7, "target_ranks": 15000,
                  "per_m": 3}}
BIG_SEED = 2 ** 31 + 12345


def test_head_shapes_match_the_serving_benchmark():
    from benchmarks.perf_serve import head_shapes
    assert gen.head_shapes() == head_shapes()
    assert gen.mix_shapes(MIX["shapes"]) == [
        (3, 41), (3, 42), (3, 43), (4, 24), (4, 25), (4, 26), (5, 19),
        (5, 20), (5, 21), (6, 17), (6, 18), (7, 16), (7, 17)]


def test_open_schedule_repeats_exactly():
    a = gen.open_schedule(MIX, BIG_SEED, 2.0)
    b = gen.open_schedule(MIX, BIG_SEED, 2.0)
    assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.grads, b.grads)
    assert all(np.array_equal(x, y) for x, y in zip(a.mats, b.mats))


def test_every_seed_gets_the_same_work():
    a = gen.open_schedule(MIX, 1, 2.0)
    b = gen.open_schedule(MIX, BIG_SEED, 2.0)
    assert len(a) == len(b) == 1000
    assert a.grads.sum() == b.grads.sum() == 250
    assert np.array_equal(np.bincount(a.shape_idx),
                          np.bincount(b.shape_idx))
    assert np.allclose(np.sort(np.diff(a.due, prepend=0.0)),
                       np.sort(np.diff(b.due, prepend=0.0)))
    assert a.due[-1] == pytest.approx(2.0)
    assert not np.array_equal(a.due, b.due)
    assert [m.shape for m in a.mats] == [a.shapes[i] for i in a.shape_idx]


def test_solve_and_closed_streams_repeat_exactly():
    s1, s2 = gen.SolveStream((4, 9), BIG_SEED), gen.SolveStream((4, 9),
                                                                BIG_SEED)
    for _ in range(3):
        assert np.array_equal(s1.next(), s2.next())
    assert not np.array_equal(s1.matrix(0), s1.matrix(1))
    mix = {"shapes": [[4, 9], [5, 9]], "grad_frac": 0.5}
    c1 = gen.ClosedStream(mix, BIG_SEED, block=8)
    c2 = gen.ClosedStream(mix, BIG_SEED, block=8)
    m1, g1 = c1.take(3, 10)
    m2, g2 = c2.take(3, 10)
    assert g1 == g2 and all(np.array_equal(x, y) for x, y in zip(m1, m2))
    _, g = c1.take(0, 8)
    assert sum(g) == 4


class _StallingServer:
    """Answers each request ``service_s`` after it is sent; the first
    ``submit_many`` stalls the sender for ``stall_s``."""

    def __init__(self, stall_s: float, service_s: float = 0.001):
        self.stall_s, self.service_s = stall_s, service_s
        self.seq = 0
        self.timers = []

    def submit_many(self, mats, grads):
        if self.seq == 0:
            time.sleep(self.stall_s)
        futs = []
        for _ in mats:
            f = Future()
            f.seq = self.seq
            self.seq += 1
            t = threading.Timer(self.service_s, f.set_result, (1.0,))
            t.start()
            self.timers.append(t)
            futs.append(f)
        return futs


class _Window:
    def open(self):
        pass

    def close(self):
        pass


def test_due_time_sojourn_counts_a_stall():
    mix = {"loop": "open", "rate_per_s": 400, "shapes": [[3, 5]]}
    sched = gen.open_schedule(mix, 7, 0.5)
    server = _StallingServer(stall_s=0.25)
    rec = loops.open_loop(server, sched, 0.5, _Window())
    for t in server.timers:
        t.join()
    soj = rec["done"] - rec["due"]
    lag = rec["sent"] - rec["due"]
    # requests due during the stall were sent late, and their sojourn,
    # timed from their due time, carries that wait
    assert lag.max() >= 0.2
    assert np.all(soj >= lag)
    stalled = sched.due < 0.2
    assert np.all(soj[stalled] >= 0.25 - sched.due[stalled] - 1e-3)
    run = type("R", (), {"rec": rec})()
    p99 = metric_reader("sojourn_p99_ms")(run)
    assert p99 >= 200.0
    assert metric_reader("gen_lag_ms.latency")(run) >= 150.0


def test_a_request_that_never_comes_is_beyond_any_limit():
    rec = {"due": np.zeros(100), "done": np.full(100, 0.01),
           "results": [1.0] * 100}
    rec["done"][5] = np.nan
    rec["results"][7] = None
    run = type("R", (), {"rec": rec})()
    # 2 of 100 missing: the 99th percentile is one of them
    assert metric_reader("sojourn_p99_ms")(run) == 1e12
