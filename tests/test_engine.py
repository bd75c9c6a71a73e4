"""DetEngine/DetPlan battery: bit-identity against the pre-refactor
traced paths, plan-time validation ordering, degenerate-shape
normalization, and LRU cache semantics.

The engine's contract (DESIGN_ENGINE.md): a plan binds exactly the
statics the pre-engine paths bound and enters exactly the same jitted
programs, so routing through the engine — and re-planning after an LRU
eviction — must not move a single bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import (DetEngine, default_engine, make_batched_evaluator,
                        radic_det, radic_det_batched, radic_det_distributed,
                        validate_rank_space)
from repro.core.pascal import INT32_MAX, binom_table, comb
from repro.core.radic import _radic_det_batched_flat, _radic_det_flat

SHAPES = [(1, 5), (2, 6), (3, 8), (3, 3)]


def _statics(m, n, chunk):
    """The pre-refactor per-shape recipe, spelled out independently:
    int32 Pascal table (x64 off in tier-1), exact total, clamped chunk."""
    total = comb(n, m)
    table = jnp.asarray(binom_table(n, m, dtype=np.int32))
    return total, table, int(min(chunk, max(total, 1)))


def _mesh():
    devs = np.array(jax.devices())
    return Mesh(devs.reshape(len(devs)), ("workers",))


# ------------------------------------------------------------- bit-identity
@pytest.mark.parametrize("m,n", SHAPES)
def test_scalar_bit_identity_vs_traced_program(m, n, rng):
    """radic_det (now engine-routed) enters the same jitted program with
    the same statics the pre-refactor wrapper bound → identical bits."""
    A = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    total, table, chunk = _statics(m, n, 64)
    want = _radic_det_flat(A, table, total, chunk, False)
    got = radic_det(A, chunk=64)
    assert float(got) == float(want)


def test_scalar_kahan_bit_identity(rng):
    A = jnp.asarray(rng.normal(size=(3, 9)).astype(np.float32))
    total, table, chunk = _statics(3, 9, 32)
    want = _radic_det_flat(A, table, total, chunk, True)
    assert float(radic_det(A, chunk=32, kahan=True)) == float(want)


@pytest.mark.parametrize("cap", [1, 2, 8])
@pytest.mark.parametrize("m,n", SHAPES)
def test_batched_bit_identity_across_capacities(m, n, cap, rng):
    """Both the traced (capacity=None) and the AOT-lowered (capacity=cap)
    plans are the same XLA program as the direct jitted call."""
    As = jnp.asarray(rng.normal(size=(cap, m, n)).astype(np.float32))
    total, table, chunk = _statics(m, n, 64)
    want = np.asarray(_radic_det_batched_flat(As, table, total, chunk))
    eng = DetEngine()
    traced = eng.plan(m, n, chunk=64)
    assert not traced.lowered
    np.testing.assert_array_equal(np.asarray(traced(As)), want)
    aot = eng.plan(m, n, capacity=cap, chunk=64)
    assert aot.lowered
    np.testing.assert_array_equal(np.asarray(aot(As)), want)
    np.testing.assert_array_equal(
        np.asarray(radic_det_batched(As, chunk=64)), want)


def test_pallas_routing_bit_identity(rng):
    """The engine's pallas route is the same ops entry point the
    pre-refactor wrappers called directly."""
    from repro.kernels import ops
    As = jnp.asarray(rng.normal(size=(3, 2, 7)).astype(np.float32))
    want = np.asarray(ops.radic_det_batched_pallas(As, q_start=0,
                                                   count=comb(7, 2)))
    plan = DetEngine().plan(2, 7, backend="pallas")
    np.testing.assert_array_equal(np.asarray(plan(As)), want)
    A = As[0]
    want_s = float(ops.radic_det_pallas(A, q_start=0, count=comb(7, 2)))
    assert float(radic_det(A, backend="pallas")) == want_s


# --------------------------------------------------- validation before dispatch
def test_pallas_overflow_guard_runs_at_plan_time():
    """Regression (ISSUE 3 satellite): the pallas path historically
    dispatched before the C(n, m) width guard.  C(40, 16) > 2**31 must
    raise OverflowError at *plan* time for every batched pallas entry
    point — binding an evaluator must already fail, not its first call.
    The scalar plan runs past int32 as rank windows, planned (not
    dispatched) at plan time, and refuses a gradient before any kernel."""
    assert comb(40, 16) > INT32_MAX
    with pytest.raises(OverflowError):
        DetEngine().plan(16, 40, backend="pallas")
    with pytest.raises(OverflowError):
        make_batched_evaluator(16, 40, backend="pallas")
    plan = DetEngine().plan(16, 40, batched=False, backend="pallas")
    assert plan.windows == -(-comb(40, 16) // (2 ** 31 - 2 ** 16))
    with pytest.raises(OverflowError):
        jax.grad(lambda A: radic_det(A, backend="pallas"))(
            jnp.ones((16, 40), jnp.float32))
    with pytest.raises(OverflowError):
        radic_det_batched(jnp.ones((2, 16, 40), jnp.float32),
                          backend="pallas")


def test_jnp_overflow_guard_points_at_grains():
    if jax.config.jax_enable_x64:
        pytest.skip("int32 guard is bypassed under x64")
    with pytest.raises(OverflowError, match="grains"):
        DetEngine().plan(16, 40)
    with pytest.raises(OverflowError):
        validate_rank_space(16, 40)


def test_grains_mode_has_no_width_limit():
    # C(40, 16) overflows int32 but host-bigint grain starts don't care
    assert validate_rank_space(16, 40, mesh_grains=True) == comb(40, 16)


# --------------------------------------------------------- degenerate m > n
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_degenerate_batched_is_device_program(backend, rng):
    """Regression (ISSUE 3 satellite): make_batched_evaluator's m > n
    fast-path used to hand back a host closure that ignored an explicit
    backend/mesh; the engine normalizes it to a jitted zeros *device*
    program for every configuration."""
    ev = make_batched_evaluator(4, 2, backend=backend)
    out = ev(rng.normal(size=(3, 4, 2)).astype(np.float32))
    assert isinstance(out, jax.Array)
    assert out.shape == (3,) and not np.asarray(out).any()


def test_degenerate_batched_with_mesh_is_device_program(rng):
    ev = make_batched_evaluator(4, 2, mesh=_mesh())
    out = ev(rng.normal(size=(3, 4, 2)).astype(np.float32))
    assert isinstance(out, jax.Array)
    assert out.shape == (3,) and not np.asarray(out).any()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_degenerate_scalar_is_device_zero(backend):
    out = radic_det(jnp.ones((4, 2), jnp.float32), backend=backend)
    assert isinstance(out, jax.Array) and float(out) == 0.0
    out = radic_det_distributed(jnp.ones((4, 2), jnp.float32),
                                backend=backend)
    assert isinstance(out, jax.Array) and float(out) == 0.0


# ------------------------------------------------------------- cache semantics
def test_plan_cache_hit_returns_same_plan():
    eng = DetEngine()
    p1 = eng.plan(2, 6, capacity=4)
    p2 = eng.plan(2, 6, capacity=4)
    assert p1 is p2
    assert eng.cache_info()["hits"] == 1
    # any key ingredient changes → a different plan
    assert eng.plan(2, 6, capacity=8) is not p1
    assert eng.plan(2, 6, capacity=4, chunk=64) is not p1
    assert eng.plan(2, 6) is not p1


def test_lru_eviction_and_replan_bit_identity(rng):
    """Evicted shapes re-plan and reproduce identical bits — the property
    that makes the cache bound safe for long-tail shape traffic."""
    eng = DetEngine(max_plans=2)
    inputs = {}
    before = {}
    for m, n in [(1, 5), (2, 6), (3, 8)]:
        As = jnp.asarray(rng.normal(size=(4, m, n)).astype(np.float32))
        inputs[(m, n)] = As
        before[(m, n)] = np.asarray(eng.plan(m, n, capacity=4, chunk=64)(As))
    info = eng.cache_info()
    assert info["size"] == 2 and info["evictions"] == 1
    # (1, 5) was evicted (LRU); re-planning must not move a bit
    keys = [(k.m, k.n) for k in eng.cached_keys()]
    assert (1, 5) not in keys
    for m, n in [(1, 5), (2, 6), (3, 8)]:
        again = np.asarray(eng.plan(m, n, capacity=4, chunk=64)(
            inputs[(m, n)]))
        np.testing.assert_array_equal(again, before[(m, n)])
    assert eng.cache_info()["size"] == 2  # still bounded after re-plans


def test_lru_order_refreshes_on_hit():
    eng = DetEngine(max_plans=2)
    eng.plan(1, 5)
    eng.plan(2, 6)
    eng.plan(1, 5)  # refresh: (2, 6) is now the eviction candidate
    eng.plan(3, 8)
    keys = [(k.m, k.n) for k in eng.cached_keys()]
    assert (2, 6) not in keys and (1, 5) in keys and (3, 8) in keys


def test_mesh_plans_are_cached_across_calls(rng):
    """Equal meshes hash equal, so repeated distributed calls reuse one
    planned worker (grain starts unranked once, not per call)."""
    eng = DetEngine()
    A = jnp.asarray(rng.normal(size=(2, 6)).astype(np.float32))
    got1 = float(eng.det(A, mesh=_mesh()))
    got2 = float(eng.det(A, mesh=_mesh()))
    info = eng.cache_info()
    assert info["misses"] == 1 and info["hits"] == 1
    assert got1 == got2


def test_engine_validation_errors():
    eng = DetEngine()
    with pytest.raises(ValueError):
        eng.plan(2, 6, backend="cuda")
    with pytest.raises(ValueError):
        eng.plan(2, 6, batched=True, kahan=True)
    with pytest.raises(ValueError):
        eng.plan(2, 6, batched=False, capacity=4)
    with pytest.raises(ValueError):
        DetEngine(max_plans=0)


def test_default_engine_is_shared_and_swappable():
    from repro.core import set_default_engine
    assert default_engine() is default_engine()
    custom = DetEngine(max_plans=4)
    set_default_engine(custom)
    try:
        assert default_engine() is custom
        radic_det(jnp.ones((2, 5), jnp.float32), chunk=16)
        assert custom.cache_info()["size"] == 1
    finally:
        set_default_engine(None)
    assert default_engine() is not custom


def test_aot_donated_lowering_bit_identical(rng, monkeypatch):
    """The donated AOT lowering (TPU/GPU hot path) is the same XLA
    program: forcing it on (CPU ignores the donation hint with a
    warning, which is exactly why the engine gates it) must produce
    bit-identical results to the default lowering."""
    import warnings

    from repro.core import engine as E

    m, n, cap = 3, 8, 4
    As = rng.normal(size=(cap, m, n)).astype(np.float32)
    want = np.asarray(DetEngine().plan(m, n, batched=True, capacity=cap,
                                       dtype=np.float32)(jnp.asarray(As)))
    monkeypatch.setattr(E, "_donation_supported", lambda: True)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # CPU: "donated buffers not usable"
        plan = DetEngine().plan(m, n, batched=True, capacity=cap,
                                dtype=np.float32)
        got = np.asarray(plan(jnp.asarray(As)))
    assert plan.lowered is True
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("plan_kw,rows", [
    ({"batched": False, "backend": "pallas"}, 8),
    ({"capacity": 1, "backend": "pallas"}, 8),
    ({"capacity": 64, "backend": "pallas"}, 1),
    ({"capacity": None, "backend": "pallas"}, None),
    ({"batched": False, "backend": "jnp"}, None),
    ({"batched": False, "backend": "pallas", "mesh": "mesh"}, 8),
    ({"capacity": 1, "backend": "pallas", "mesh": "mesh",
      "batch_axis": "workers"}, 8),
])
def test_plan_reports_kernel_rank_rows(plan_kw, rows):
    """The engagement record of the value kernel's layout: 8 rank rows
    of one matrix per sublane group where each kernel call sees B = 1
    (the paper's solve, per device over a mesh too), 1 for a stack (a
    serving bucket at capacity 64), None without a kernel or a fixed
    batch."""
    if plan_kw.get("mesh") == "mesh":
        plan_kw = dict(plan_kw, mesh=_mesh())
    plan = DetEngine().plan(3, 7, **plan_kw)
    assert plan.rank_rows == rows


def test_plan_build_span_carries_rank_rows(tmp_path):
    from jax.profiler import ProfileData
    eng = DetEngine()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.plan(3, 7, batched=False, backend="pallas")
        eng.plan(3, 7, capacity=64, backend="pallas")
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    planes = ProfileData.from_file(str(path)).planes
    stats = [dict(ev.stats) for plane in planes for line in plane.lines
             for ev in line.events if ev.name == "det.plan.build"]
    assert [s.get("rank_rows") for s in stats] == [8, 1]


# B = 1 and windowed plans gather with one bf16 pass over A's exact split;
# a stack (a serving value bucket at 64, a gradient bucket at 16) at
# Precision.HIGHEST, as every gradient kernel does.
GATHER_PLANS = [
    ((3, 7), {"batched": False, "backend": "pallas"}, "bf16x3"),
    ((12, 38), {"batched": False, "backend": "pallas"}, "bf16x3"),
    ((3, 7), {"capacity": 64, "backend": "pallas"}, "f32_highest"),
    ((3, 7), {"capacity": 16, "backend": "pallas"}, "f32_highest"),
]


@pytest.mark.parametrize("shape,plan_kw,gather", GATHER_PLANS + [
    ((3, 7), {"capacity": None, "backend": "pallas"}, None),
    ((3, 7), {"batched": False, "backend": "jnp"}, None),
])
def test_plan_reports_kernel_gather(shape, plan_kw, gather):
    """The engagement record of the value kernel's gather, derived from
    the key as ``rank_rows`` is: None without a kernel or a fixed batch."""
    plan = DetEngine().plan(*shape, **plan_kw)
    assert plan.gather == gather
    assert (gather == "bf16x3") == (plan.rank_rows == 8)


def test_plan_build_span_carries_gather(tmp_path):
    from jax.profiler import ProfileData
    eng = DetEngine()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for shape, plan_kw, _ in GATHER_PLANS:
            eng.plan(*shape, **plan_kw)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    planes = ProfileData.from_file(str(path)).planes
    stats = [dict(ev.stats) for plane in planes for line in plane.lines
             for ev in line.events if ev.name == "det.plan.build"]
    assert [s.get("gather") for s in stats] == [g for *_, g in GATHER_PLANS]
