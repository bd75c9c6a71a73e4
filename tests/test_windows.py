"""Rank windows: one matrix's Radic determinant past int32 on the Pallas
path.

The plan cuts ``[0, C(n, m))`` into the fewest windows of at most
``engine.WINDOW_RANKS`` ranks and unranks each window's start on the
host; the windowed kernel unranks every lane's int32 offset relative to
its window's start.  Here: the cut tiles the rank space; the windowed
unranking, as plain jnp, equals ``unrank_py`` of the global rank; the
kernel in interpret mode, with the window limit patched small, equals
the oracle; the counters and spans report the windows; and what stays
refused past int32 (batched plans, on one device or over a mesh, and
gradients) says so.  The mesh's windowed path runs in
``tests/test_distributed.py``.
"""

import functools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DetEngine, comb, radic_det, radic_det_oracle,
                        set_default_engine, unrank_py)
from repro.core import engine
from repro.core.pascal import INT32_MAX
from repro.kernels import ops
from repro.kernels.common import unrank_window_lanes
from repro.kernels.radic_windowed import radic_windowed_partial_pallas


@pytest.fixture
def own_engine():
    """A default engine of the test's own: plans built under a patched
    window limit must not outlive the patch."""
    set_default_engine(DetEngine())
    yield
    set_default_engine(None)


def _limit(monkeypatch, ranks):
    monkeypatch.setattr(engine, "WINDOW_RANKS", ranks)


# ------------------------------------------------------------- the plan
@pytest.mark.parametrize("n,m,L,q_start", [
    (10, 4, 7, 0), (10, 4, 210, 0), (10, 4, 209, 0), (11, 5, 50, 0),
    (12, 5, 13, 0), (12, 5, 97, 100), (9, 1, 2, 3), (14, 6, 1000, 1)])
def test_windows_tile_the_rank_range(monkeypatch, n, m, L, q_start):
    """Contiguous, in order, each at most L long, together exactly
    [q_start, C(n, m)), and no fewer windows would do."""
    _limit(monkeypatch, L)
    count = comb(n, m) - q_start
    w = engine.plan_rank_windows(m, n, q_start, count)
    assert len(w) == -(-count // L) == len(w.lengths) == len(w.slacks)
    assert max(w.lengths) <= L and sum(w.lengths) == count
    assert w.starts[0] == q_start
    for a, la, b in zip(w.starts, w.lengths, w.starts[1:]):
        assert a + la == b
    assert w.slacks.shape == (len(w), n) and w.slacks.dtype == np.int32


def test_window_count_past_the_limit(monkeypatch):
    assert engine.window_count(engine.WINDOW_RANKS) is None
    assert engine.window_count(engine.WINDOW_RANKS + 1) == 2
    assert engine.window_count(comb(38, 12)) == 2
    assert engine.WINDOW_RANKS < INT32_MAX
    _limit(monkeypatch, 100)
    assert engine.window_count(792) == 8


# ---------------------------------------------------- windowed unranking
@functools.lru_cache(maxsize=None)
def _unranker(n, m):
    return jax.jit(lambda ds, table, slack: jnp.stack(
        unrank_window_lanes(ds[None], n, m, table, slack, -1))[:, 0].T)


def _window_combos(n, m, q0, ds):
    table = jnp.asarray(ops.capped_table(n, m)).reshape(-1)
    slack = jnp.asarray(engine.window_slacks(q0, n, m))
    width = -(-len(ds) // 64) * 64       # few shapes, few compiles
    padded = np.zeros(width, np.int32)
    padded[:len(ds)] = ds
    return np.asarray(_unranker(n, m)(jnp.asarray(padded), table,
                                      slack))[:len(ds)]


@pytest.mark.parametrize("n,m,L", [(10, 4, 7), (11, 5, 50), (8, 8, 1),
                                   (9, 1, 2), (12, 6, 97), (7, 3, 35)])
def test_windowed_unranking_every_offset(n, m, L):
    """Every offset of every window equals unrank_py of its global rank."""
    total = comb(n, m)
    for q0 in range(0, total, L):
        ds = list(range(min(L, total - q0)))
        got = _window_combos(n, m, q0, ds)
        want = [unrank_py(q0 + d, n, m) for d in ds]
        assert [tuple(g) for g in got] == want


@pytest.mark.parametrize("n,m", [(38, 12), (40, 20)])
def test_windowed_unranking_random_windows_past_int32(n, m):
    """Windows of L = 2³¹ − 1 ranks anywhere in a rank space past int32:
    the first, the last and random offsets."""
    rnd = random.Random(n * 100 + m)
    L, total = INT32_MAX, comb(n, m)
    assert total > L
    for q0 in [0, total - L] + [rnd.randrange(total - L) for _ in range(4)]:
        ds = [0, 1, L - 1] + [rnd.randrange(L) for _ in range(64)]
        got = _window_combos(n, m, q0, ds)
        assert [tuple(g) for g in got] == [unrank_py(q0 + d, n, m)
                                           for d in ds]


def test_window_slacks_are_clipped_int32():
    s = engine.window_slacks(0, 40, 20)
    assert s.dtype == np.int32 and s[0] == INT32_MAX  # C(39, 19) > 2³¹
    assert (s[20:] == 0).all() and s[19] == 1         # [1..20]: last slack 1


# ------------------------------------------------- the kernel, interpreted
@pytest.mark.parametrize("m,n,limit", [(5, 12, 100), (6, 14, 700),
                                       (1, 9, 3), (6, 16, 3000)])
def test_windowed_kernel_matches_oracle(monkeypatch, own_engine, m, n,
                                        limit):
    """radic_det(A, backend="pallas") through a windowed plan of 3 or more
    windows equals the oracle on seeded matrices.  (6, 16) at 3,000: 3
    windows of 2,669 or 2,670 ranks over 2,048 lanes, so each lane walks
    a run of K = 2 and the last lanes' runs are short or empty."""
    _limit(monkeypatch, limit)
    rng = np.random.default_rng(m * 31 + n)
    plan = engine.default_engine().plan(m, n, batched=False,
                                        backend="pallas")
    assert plan.windows is not None and plan.windows >= 3
    for _ in range(2):
        A = rng.standard_normal((m, n)).astype(np.float32)
        got = float(radic_det(jnp.asarray(A), backend="pallas"))
        want = float(radic_det_oracle(A))
        assert abs(got - want) <= 2e-5 * max(1.0, abs(want))


def test_windowed_kernel_square_matrix():
    """m = n: one rank, one window, the ordinary determinant."""
    rng = np.random.default_rng(6)
    A = rng.standard_normal((6, 6)).astype(np.float32)
    w = engine.plan_rank_windows(6, 6, 0, 1)
    parts = radic_windowed_partial_pallas(
        jnp.asarray(A), jnp.asarray(ops.capped_table(6, 6)),
        np.asarray(w.lengths, np.int32), w.slacks, 256, tile=32)
    want = float(np.linalg.det(A.astype(np.float64)))
    assert parts.shape == (1,)
    assert abs(float(parts[0]) - want) <= 1e-5 * max(1.0, abs(want))


def test_window_partials_add_up(monkeypatch):
    """Each window's partial is the oracle's sum over its own ranks."""
    _limit(monkeypatch, 50)
    m, n = 4, 10
    A = np.random.default_rng(3).standard_normal((m, n)).astype(np.float32)
    w = engine.plan_rank_windows(m, n, 0, comb(n, m))
    parts = np.asarray(radic_windowed_partial_pallas(
        jnp.asarray(A), jnp.asarray(ops.capped_table(n, m)),
        np.asarray(w.lengths, np.int32), w.slacks, 256, tile=32))
    A64 = A.astype(np.float64)
    r = m * (m + 1) // 2
    for q0, length, got in zip(w.starts, w.lengths, parts):
        want = sum((-1) ** ((r + sum(c)) & 1)
                   * np.linalg.det(A64[:, [x - 1 for x in c]])
                   for c in (unrank_py(q, n, m)
                             for q in range(q0, q0 + length)))
        assert abs(got - want) <= 1e-5 * max(1.0, abs(want))


# ------------------------------------------ counters, spans, refusals
def test_int32_plans_have_no_windows():
    eng = DetEngine()
    for kw in ({"batched": False, "backend": "pallas"},
               {"capacity": 64, "backend": "pallas"},
               {"batched": False, "backend": "jnp"}):
        assert eng.plan(12, 32, **kw).windows is None
    assert eng.plan(3, 7, batched=False, backend="pallas").windows is None


def test_paper_past_int32_plans_two_windows():
    """(12, 38) plans on the host alone: two windows, no compile."""
    plan = DetEngine().plan(12, 38, batched=False, backend="pallas")
    assert plan.windows == 2 and plan.rank_rows == 8
    assert plan.total == comb(38, 12) > INT32_MAX


def test_cache_info_counts_dispatched_windows(monkeypatch):
    _limit(monkeypatch, 100)
    eng = DetEngine()
    A = jnp.asarray(np.random.default_rng(1).standard_normal(
        (5, 12)).astype(np.float32))
    assert eng.cache_info()["windows"] == 0
    plan = eng.plan(5, 12, batched=False, backend="pallas")
    plan(A)
    plan(A)
    assert eng.cache_info()["windows"] == 2 * plan.windows == 16
    # 8 windows of at most 99 ranks: one grid step of 8 · 256 each
    assert eng.cache_info()["grid_ranks"] == 2 * 8 * 1 * 8 * 256


def test_plan_spans_carry_the_windows(monkeypatch, tmp_path):
    from jax.profiler import ProfileData
    _limit(monkeypatch, 100)
    eng = DetEngine()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.plan(5, 12, batched=False, backend="pallas")
        eng.plan(3, 7, batched=False, backend="pallas")
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = [ev for plane in ProfileData.from_file(str(path)).planes
              for line in plane.lines for ev in line.events]
    build = [dict(ev.stats) for ev in events if ev.name == "det.plan.build"]
    assert build[0]["windows"] == 8
    assert build[1].get("windows") in (None, "None")    # no windows
    (win,) = [dict(ev.stats) for ev in events
              if ev.name == "det.plan.windows"]
    assert win["windows"] == 8 and win["window_ranks"] == 99


def test_batched_past_int32_refused():
    with pytest.raises(OverflowError, match="one matrix"):
        DetEngine().plan(12, 38, capacity=1, backend="pallas")
    with pytest.raises(OverflowError, match="one matrix"):
        ops.radic_det_batched_pallas(jnp.ones((2, 12, 38), jnp.float32))


def _one_device_mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]), ("workers",))


def test_mesh_batched_past_int32_refused():
    """A scalar Pallas mesh plan runs past int32 as rank windows; a
    batched one is still refused, with a message that says so."""
    with pytest.raises(OverflowError, match="batched"):
        DetEngine().plan(12, 38, capacity=1, backend="pallas",
                         mesh=_one_device_mesh())


def test_mesh_gradient_past_int32_refused_before_any_kernel(monkeypatch):
    """A scalar Pallas mesh plan past int32 builds no gradient program:
    jax.grad and plan.grad raise at once and the kernel never runs."""
    ran = []
    monkeypatch.setattr(ops, "radic_windowed_partial_pallas",
                        lambda *a, **k: ran.append(1))
    eng = DetEngine()
    plan = eng.plan(12, 38, batched=False, backend="pallas",
                    mesh=_one_device_mesh())
    assert plan.windows == 2
    A = jnp.ones((12, 38), jnp.float32)
    with pytest.raises(OverflowError, match="values only"):
        jax.grad(plan.differentiable)(A)
    with pytest.raises(OverflowError, match="values only"):
        plan.grad(A, 1.0)
    assert ran == [] and eng.cache_info()["windows"] == 0


def test_gradient_past_int32_refused_before_any_kernel(monkeypatch):
    """No gradient program is built past int32: jax.grad and plan.grad
    raise at once, the kernel never runs, no value comes back."""
    ran = []
    monkeypatch.setattr(ops, "radic_windowed_partial_pallas",
                        lambda *a, **k: ran.append(1))
    eng = DetEngine()
    plan = eng.plan(12, 38, batched=False, backend="pallas")
    A = jnp.ones((12, 38), jnp.float32)
    with pytest.raises(OverflowError, match="values only"):
        jax.grad(plan.differentiable)(A)
    with pytest.raises(OverflowError, match="values only"):
        plan.grad(A, 1.0)
    assert ran == [] and eng.cache_info()["windows"] == 0


@pytest.mark.parametrize("m,n", [(6, 215)])
def test_rank_range_deep_past_int32(m, n):
    """4,096 ranks from q_start = 1e11 through radic_det_pallas(A, q_start,
    count) against a float64 sum over unrank_py's combinations."""
    q_start, count = 10 ** 11, 4096
    assert comb(n, m) > q_start + count and q_start > INT32_MAX
    A = np.random.default_rng(m + n).standard_normal((m, n)).astype(
        np.float32)
    got = float(ops.radic_det_pallas(jnp.asarray(A), q_start=q_start,
                                     count=count))
    A64 = A.astype(np.float64)
    cols = np.array([unrank_py(q, n, m)
                     for q in range(q_start, q_start + count)]) - 1
    signs = np.where((m * (m + 1) // 2 + cols.sum(axis=1) + m) & 1, -1, 1)
    dets = np.linalg.det(np.transpose(A64[:, cols], (1, 0, 2)))
    want = float(np.sum(signs * dets))
    scale = float(np.sqrt(np.sum(dets ** 2)))
    assert abs(got - want) <= 1e-5 * scale
