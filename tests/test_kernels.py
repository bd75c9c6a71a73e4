"""The kernels and their in-kernel routines, swept over shapes against
the numpy oracles (kernels in interpret mode)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pascal import comb
from repro.kernels import ops, ref


def _det_slabs_of(mats: np.ndarray) -> np.ndarray:
    """``common.det_slabs`` over a ``(B, m, m)`` stack: B padded with
    identity matrices to whole ``(8, T)`` slabs, matrix ``s·T + t`` at
    (sublane s, lane t), as the B = 1 kernel lays out its rank rows."""
    from repro.kernels.common import det_slabs
    B, m, _ = mats.shape
    T = -(-B // 8)
    pad = np.broadcast_to(np.eye(m, dtype=mats.dtype), (8 * T - B, m, m))
    full = np.concatenate([mats, pad]).reshape(8, T, m, m)
    slabs = [[jnp.asarray(full[:, :, r, c]) for c in range(m)]
             for r in range(m)]
    return np.asarray(det_slabs(slabs)).reshape(-1)[:B]


@pytest.mark.parametrize("B,m", [(1, 1), (3, 2), (7, 3), (130, 4),
                                 (64, 5), (5, 8), (256, 2)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_minor_det_sweep(B, m, dtype, rng):
    mats = rng.normal(size=(B, m, m)).astype(dtype)
    got = _det_slabs_of(mats)
    want = ref.minor_det_ref(mats)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=1e-4)


def test_minor_det_singular_and_permuted(rng):
    m = 4
    a = rng.normal(size=(m, m)).astype(np.float32)
    sing = a.copy()
    sing[2] = sing[0]  # rank-deficient
    perm = a[[1, 0, 2, 3]]  # one swap -> -det
    mats = np.stack([a, sing, perm, np.eye(m, dtype=np.float32)])
    got = _det_slabs_of(mats)
    np.testing.assert_allclose(
        got, [np.linalg.det(a), 0.0, -np.linalg.det(a), 1.0],
        rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("n,m", [(8, 5), (6, 3), (10, 2), (12, 6),
                                 (5, 5), (9, 1), (16, 3)])
@pytest.mark.parametrize("rows", [1, 8])
def test_unrank_sweep(n, m, rows):
    """``common.unrank_lanes`` on the kernels' two row layouts: one
    ``(1, T)`` row shared by a batch (B ≥ 2) and the ``(8, T)`` rank rows
    of one matrix (B = 1), lane (s, t) holding rank ``s·T + t``."""
    from repro.core.pascal import binom_table
    from repro.kernels.common import unrank_lanes
    total = comb(n, m)
    T = -(-total // (rows * 128)) * 128
    qs = np.minimum(np.arange(rows * T), total - 1).astype(np.int32)
    table = jnp.asarray(binom_table(n, m, dtype=np.int32).reshape(-1))
    combo = unrank_lanes(jnp.asarray(qs.reshape(rows, T)), n, m, table)
    got = np.stack([np.asarray(c).reshape(-1) for c in combo], axis=1)
    want = ref.unrank_ref(qs[:total], n, m)
    assert (got[:total] == want).all()


@pytest.mark.parametrize("m,n", [(2, 6), (3, 7), (4, 8), (5, 8),
                                 (1, 5), (3, 3), (2, 12)])
def test_radic_fused_full(m, n, rng):
    A = rng.normal(size=(m, n)).astype(np.float32)
    got = float(ops.radic_det_pallas(jnp.asarray(A), tile=32))
    want = ref.radic_det_oracle(A)
    assert abs(got - want) <= 2e-3 * max(1.0, abs(want))


@pytest.mark.parametrize("q0,cnt", [(0, 1), (10, 17), (50, 6), (0, 56)])
def test_radic_fused_partial_ranges(q0, cnt, rng):
    A = rng.normal(size=(3, 8)).astype(np.float32)
    got = float(ops.radic_det_pallas(jnp.asarray(A), q_start=q0,
                                     count=cnt, tile=8))
    want = ref.radic_partial_ref(A, q0, cnt)
    assert abs(got - want) <= 1e-3 * max(1.0, abs(want))


def test_radic_fused_partials_compose(rng):
    """Grain partials sum to the full determinant (reduction idempotence)."""
    A = rng.normal(size=(3, 9)).astype(np.float32)
    total = comb(9, 3)
    cuts = [0, 20, 21, 60, total]
    parts = [float(ops.radic_det_pallas(jnp.asarray(A), q_start=a,
                                        count=b - a, tile=16))
             for a, b in zip(cuts[:-1], cuts[1:])]
    want = ref.radic_det_oracle(A)
    assert abs(sum(parts) - want) <= 2e-3 * max(1.0, abs(want))


def test_bf16_input_promoted(rng):
    """bf16 inputs are computed in f32 inside the kernel."""
    A = rng.normal(size=(3, 7)).astype(np.float32)
    got = float(ops.radic_det_pallas(jnp.asarray(A, jnp.bfloat16), tile=32))
    want = ref.radic_det_oracle(A.astype(np.float32))
    # bf16 storage of A costs precision; tolerance is loose by design
    assert abs(got - want) <= 0.05 * max(1.0, abs(want))


def test_int32_guard():
    """Past int32 only the scalar value runs (as rank windows); the
    batched and gradient entry points keep the int32 guard."""
    with pytest.raises(OverflowError):
        ops.radic_det_batched_pallas(jnp.ones((1, 16, 40), jnp.float32))
    with pytest.raises(OverflowError):
        ops.radic_det_grad_pallas(jnp.ones((16, 40), jnp.float32), 1.0)


# --------------------------------------------- B = 1: rank rows on sublanes
# At B = 1 the value kernel puts 8 rank rows of the one matrix on its
# sublanes, 8·tile ranks per grid step (radic_fused.rank_rows).  With
# tile 8 a step covers 64 ranks: the counts below fall short of one row,
# inside the first step, or past whole steps with a tail.

@pytest.mark.parametrize("m,n", [(1, 5), (1, 9), (4, 4), (6, 6), (2, 12),
                                 (3, 10), (5, 11)])
def test_rank_rows_full(m, n, rng):
    A = rng.normal(size=(m, n)).astype(np.float32)
    got = float(ops.radic_det_pallas(jnp.asarray(A), tile=8))
    want = ref.radic_det_oracle(A)
    assert abs(got - want) <= 2e-3 * max(1.0, abs(want))


@pytest.mark.parametrize("m,n,q0,cnt", [
    (3, 10, 3, 5), (3, 10, 5, 61), (3, 10, 13, 64), (3, 10, 61, 59),
    (3, 10, 9, 111), (3, 10, 119, 1),
    # runs of K > 1 ranks per lane, the last lanes short or empty
    (6, 16, 0, 8008), (4, 16, 3, 1800)])
def test_rank_rows_partial_ranges(m, n, q0, cnt, rng):
    """Ranges of C(10, 3) = 120 whose start falls inside a sub-tile, and
    ranges longer than a step: the whole (6, 16) rank space, K = 126
    ranks per lane, and K = 29 over (4, 16) with the last lane empty."""
    A = rng.normal(size=(m, n)).astype(np.float32)
    got = float(ops.radic_det_pallas(jnp.asarray(A), q_start=q0, count=cnt,
                                     tile=8))
    want = ref.radic_partial_ref(A, q0, cnt)
    assert abs(got - want) <= 1e-3 * max(1.0, abs(want))


@pytest.mark.parametrize("m,n,cuts", [
    (3, 10, [0, 5, 69, 70, 120]), (3, 10, [0, 64, 120]),
    (3, 10, [0, 1, 2, 119, 120]),
    (4, 16, [0, 1, 63, 64, 65, 1819, 1820])])
def test_rank_rows_partials_compose(m, n, cuts, rng):
    A = rng.normal(size=(m, n)).astype(np.float32)
    parts = [float(ops.radic_det_pallas(jnp.asarray(A), q_start=a,
                                        count=b - a, tile=8))
             for a, b in zip(cuts[:-1], cuts[1:])]
    for a, b, got in zip(cuts[:-1], cuts[1:], parts):
        want = ref.radic_partial_ref(A, a, b - a)
        assert abs(got - want) <= 1e-3 * max(1.0, abs(want))
    whole = float(ops.radic_det_pallas(jnp.asarray(A), tile=8))
    want = ref.radic_det_oracle(A)
    assert abs(sum(parts) - want) <= 2e-3 * max(1.0, abs(want))
    assert abs(sum(parts) - whole) <= 2e-3 * max(1.0, abs(want))


@pytest.mark.parametrize("q0,cnt", [(0, 120), (7, 100)])
def test_rank_rows_match_the_batch_layout(q0, cnt, rng):
    """B = 1 (rank rows) and the same matrix inside a B = 8 stack
    (matrices on sublanes) agree within the kernel's f32 tolerance."""
    As = rng.normal(size=(8, 3, 10)).astype(np.float32)
    one = float(ops.radic_det_batched_pallas(jnp.asarray(As[3:4]), q0, cnt,
                                             tile=8)[0])
    eight = float(ops.radic_det_batched_pallas(jnp.asarray(As), q0, cnt,
                                               tile=8)[3])
    assert abs(one - eight) <= 1e-5 * max(1.0, abs(eight))


def test_rank_rows_by_batch():
    from repro.kernels.radic_fused import rank_rows
    assert [rank_rows(b) for b in (1, 2, 7, 8, 64)] == [8, 1, 1, 1, 1]


# The B = 1 gather: one bf16 pass over A's three-part split, exact.

def _split_inputs(kind, rng):
    if kind == "exponents":
        x = (rng.uniform(1, 2, 4096) * 2.0 ** rng.integers(-90, 91, 4096)
             * rng.choice([-1, 1], 4096))
    elif kind == "zeros":
        x = np.array([0.0, -0.0, 1.0, -1.0])
    elif kind == "powers_of_two":
        x = np.concatenate([2.0 ** np.arange(-90, 91),
                            -(2.0 ** np.arange(-90, 91))])
    else:  # all 24 significand bits set, both signs, across exponents
        x = (2.0 - 2.0 ** -23) * 2.0 ** np.arange(-90, 91)
        x = np.concatenate([x, -x])
    return x.astype(np.float32)


@pytest.mark.parametrize("kind", ["exponents", "zeros", "powers_of_two",
                                  "full_significand"])
def test_split_bf16x3_is_exact(kind, rng):
    """The three parts are bf16 values and re-add in f32 to A bit for bit
    in every order, as the MXU's accumulator may add them."""
    from repro.kernels.common import split_bf16x3
    x = _split_inputs(kind, rng)
    parts = split_bf16x3(jnp.asarray(x))
    assert parts.dtype == jnp.bfloat16 and parts.shape == (3, *x.shape)
    hi, mid, lo = np.asarray(parts.astype(jnp.float32))
    for p in (hi, mid, lo):
        assert not (p.view(np.uint32) & 0xFFFF).any()
    for back in ((hi + mid) + lo, (hi + lo) + mid, (mid + lo) + hi):
        assert back.dtype == np.float32
        np.testing.assert_array_equal(back.view(np.uint32),
                                      x.view(np.uint32))


@pytest.mark.parametrize("m,n", [(12, 32), (12, 38), (6, 6)])
def test_rank_row_slabs_gather_exactly(m, n, rng):
    """Every gathered entry is ``A[a, combos[k] − 1]`` bit for bit, over
    entries whose exponents span 2⁻⁶⁰…2⁶⁰."""
    from repro.kernels.common import rank_row_operand, rank_row_slabs
    S, T = 8, 128
    A = (rng.normal(size=(m, n)) * 2.0 ** rng.integers(-60, 61, (m, n))
         ).astype(np.float32)
    combos = np.sort(np.stack([rng.permutation(n)[:m] + 1
                               for _ in range(S * T)]), axis=1)
    rows = [jnp.asarray(combos[:, k].reshape(S, T), jnp.int32)
            for k in range(m)]
    M = rank_row_slabs(rank_row_operand(jnp.asarray(A)), rows)
    for k in range(m):
        cols = combos[:, k].reshape(S, T) - 1
        for a in range(m):
            got = np.asarray(M[k][a])
            assert got.dtype == np.float32
            np.testing.assert_array_equal(got.view(np.uint32),
                                          A[a][cols].view(np.uint32))


def _run_tally(count, run, tile, grid):
    """Over grid steps ``0 .. grid − 1`` of :func:`lane_runs`: each lane's
    number of valid steps and its last valid step (−1 if none)."""
    import jax
    from repro.kernels.radic_fused import SUBLANES, lane_runs
    chunk = 1024

    def body(c, carry):
        hits, last = carry
        pid = (c * chunk + jnp.arange(chunk, dtype=jnp.int32))[:, None, None]
        valid, _ = lane_runs(pid, count, run, tile)
        valid = valid & (pid < grid)
        return (hits + valid.sum(axis=0, dtype=jnp.int32),
                jnp.maximum(last, jnp.where(valid, pid, -1).max(axis=0)))

    zero = jnp.zeros((SUBLANES, tile), jnp.int32)
    hits, last = jax.jit(lambda: jax.lax.fori_loop(
        0, -(-grid // chunk), body, (zero, zero - 1)))()
    return np.asarray(hits, np.int64), np.asarray(last, np.int64)


@pytest.mark.parametrize("rows", [1, 8])
def test_lane_ranks_bounded_by_count(rows):
    """The in-kernel rank offsets, outside a kernel: a grid that runs
    past a range ending near 2³¹ − 1 forms no offset beyond the range,
    so nothing wraps int32, and exactly ``count`` (lane, step) pairs are
    valid.  ``rows`` 1: the tile layout of a stack (B ≥ 2), each step
    its own tile.  ``rows`` 8: the run layout of B = 1, each lane its own
    run of ranks, walked by successors from the one start it unranks; the
    runs' valid steps tile the range, so each rank appears once."""
    from repro.kernels.radic_fused import (_runs, _steps, lane_ranks,
                                           lane_runs)
    tile = 256
    step = rows * tile
    q0 = 5
    count = 2 ** 31 - 1 - q0 - 3            # ends 3 ranks short of 2³¹ − 1
    grid = -(-count // step) + 2            # two steps past the range
    assert grid * step > 2 ** 31 - 1        # the old offsets would wrap
    if rows == 8:
        count_, run = (int(x) for x in _runs(count, step))
        assert count_ == count and run == -(-count // step)
        _, start = lane_runs(jnp.int32(0), jnp.int32(count), jnp.int32(run),
                             tile)
        start = np.asarray(start, np.int64)
        lane = np.arange(step).reshape(rows, tile)
        np.testing.assert_array_equal(start, np.minimum(lane * run,
                                                        count - 1))
        assert (q0 + start >= q0).all() and (q0 + start < q0 + count).all()
        hits, last = _run_tally(count, run, tile, grid)
        np.testing.assert_array_equal(last, hits - 1)   # a prefix of steps
        assert hits.sum() == count
        hits, start = hits.reshape(-1), start.reshape(-1)
        ends = np.cumsum(hits)                           # runs tile the range
        live = hits > 0
        np.testing.assert_array_equal(start[live], (ends - hits)[live])
        return
    full, tail = (int(x) for x in _steps(count, step))
    assert full * step + tail == count
    seen = 0
    for pid in (0, full - 1, full, full + 1, grid - 1):
        valid, qs = lane_ranks(jnp.int32(pid), jnp.int32(q0),
                               jnp.int32(full), jnp.int32(tail), tile)
        valid, qs = np.asarray(valid), np.asarray(qs, np.int64)
        assert (qs >= q0).all() and (qs <= q0 + count).all()
        want = q0 + pid * step + np.arange(step).reshape(rows, tile)
        np.testing.assert_array_equal(qs[valid], want[valid])
        seen += int(valid.sum())
        if pid < full:
            assert valid.all()
        elif pid > full:
            assert not valid.any()
    assert seen == 2 * step + tail


@pytest.mark.parametrize("n,m", [(5, 1), (10, 3), (32, 12), (38, 12),
                                 (6, 6)])
def test_successor_lanes(n, m):
    """``common.successor_lanes`` on ``(8, T)`` slabs, as the run layout
    reads them, against ``successor_py``: random ranks, the first member,
    and the last member, which maps to itself."""
    import random
    from repro.core.unrank import successor_py, unrank_py
    from repro.kernels.common import successor_lanes
    S, T = 8, 128
    total = comb(n, m)
    rnd = random.Random(n * 100 + m)
    qs = [0, total - 1] + [rnd.randrange(total) for _ in range(S * T - 2)]
    combos = np.array([unrank_py(q, n, m) for q in qs], np.int32)
    rows = [jnp.asarray(combos[:, k].reshape(S, T)) for k in range(m)]
    got = np.stack([np.asarray(r).reshape(-1)
                    for r in successor_lanes(rows, n)], axis=1)
    for q, c, g in zip(qs, combos, got):
        want = successor_py(tuple(c), n)
        if q == total - 1:
            assert want is None and tuple(g) == tuple(c)
        else:
            assert tuple(g) == want == unrank_py(q + 1, n, m)
