"""Plan/execute engine unifying every Radic determinant evaluation path.

The paper's rank space C(n, m) factors into independent work units, and
every execution strategy in this repo — jnp flat streaming, jnp batched,
the fused Pallas kernel, mesh-distributed grains/flat — schedules those
same units differently.  Before this module each strategy carried its
own guards, Pascal-table binding and dispatch plumbing; the engine
factors the shared per-shape state into one immutable compilation
artifact (:class:`DetPlan`) and one router (:class:`DetEngine`) that
plans once and executes many (the planned-pipeline shape of Wei & Chen
2020, with the strategies swappable behind one interface per
Boix-Adserà et al. 2019).

A plan is keyed by everything that selects a distinct device program:
``(m, n, capacity, dtype, backend, mesh, …, x64)``.  Planning performs
*all* validation — ``m > n`` degeneracy, the ``C(n, m)`` integer-width
guards — **before** any backend dispatch, so no backend can be entered
with an overflowing rank space (the structural fix for the historical
``radic_det(backend="pallas")`` ordering bug).  The executable cache is
LRU-bounded (``max_plans``) for long-tail shape traffic: evicted shapes
simply re-plan, and because a plan binds exactly the statics the
pre-engine paths bound, a re-planned shape reproduces bit-identical
results (``tests/test_engine.py``).

Routing table (see DESIGN_ENGINE.md):

====================  ==========================================
plan configuration    executable
====================  ==========================================
``m > n``             jitted zeros (device program, any backend)
jnp, scalar           ``_radic_det_flat`` closure (traced jit)
jnp, batched, cap=C   the same program, AOT-lowered at (C, m, n)
jnp, batched, cap=∅   ``_radic_det_batched_flat`` closure
pallas                ``kernels.ops.radic_det[_batched]_pallas``
pallas, scalar, past  ``kernels.ops.radic_det_pallas`` over the
  ``WINDOW_RANKS``    plan's rank windows (values only)
mesh                  ``core.distributed`` maker (via compat.py)
mesh, pallas, scalar  the same maker, each device's grain as its
  past WINDOW_RANKS   own rank windows (values only)
====================  ==========================================

All shard_map use stays inside :mod:`repro.core.distributed` and hence
:mod:`repro.parallel.compat`; the engine never touches collectives.
"""

from __future__ import annotations

import functools
import hashlib
import math
import os
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Literal, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel import compat as _compat

from .pascal import INT32_MAX, binom_table, comb
from .radic import (_radic_det_batched_flat, _radic_det_batched_flat_donated,
                    _radic_det_batched_grad_flat, _radic_det_flat,
                    _radic_det_grad_flat)
from .unrank import unrank_py


def _donation_supported() -> bool:
    """Whether the active backend honors ``donate_argnums`` (TPU/GPU).
    CPU compiles donated programs fine but ignores the hint with a
    warning per lowering — so the engine only requests donation where
    it buys something.  Split out for tests to force the donated path."""
    return jax.default_backend() not in ("cpu",)

__all__ = ["DetPlan", "DetEngine", "PlanKey", "default_engine",
           "set_default_engine", "stable_key_hash", "validate_rank_space",
           "rank_table", "plan_statics", "RankWindows", "WINDOW_RANKS",
           "window_count", "window_slacks", "plan_rank_windows"]

Backend = Literal["jnp", "pallas"]


# --------------------------------------------------------- shared validation
# The longest rank window of a windowed Pallas plan: every rank offset
# the kernel forms inside a window stays in int32, with 2**16 to spare
# for the padded grid step.
WINDOW_RANKS = INT32_MAX + 1 - 2 ** 16


def validate_rank_space(m: int, n: int, *, backend: str = "jnp",
                        mesh_grains: bool = False,
                        windowed: bool = False) -> int:
    """Validate that C(n, m) fits the target backend's rank-integer width
    and return it.  This runs at *plan* time, before any backend dispatch
    — no path may enter a kernel with an overflowing rank space.

    * ``pallas`` — the kernels compute ranks in int32 regardless of x64,
      so batched and gradient programs require ``C(n, m) < 2**31``.
      ``windowed`` is the one exception: the values of one matrix (B = 1)
      past :data:`WINDOW_RANKS` ranks, on one device or over a mesh, run
      as contiguous rank windows (:func:`plan_rank_windows`), and the
      windowed kernel unranks each lane relative to its window's start,
      in int32.  The caller plans the windows; :func:`window_count` says
      whether there are any.
    * ``jnp`` — int32 ranks unless x64 is enabled (then int64).
    * ``mesh_grains`` — grain starts are unranked on the host with exact
      bigints; no width limit at all.
    """
    total = comb(n, m)
    if mesh_grains or m > n:
        return total
    if backend == "pallas":
        if total > INT32_MAX and not windowed:
            raise OverflowError(
                f"C({n},{m}) = {total} exceeds int32 (the Pallas kernels "
                "compute ranks in int32 regardless of x64).  Past int32 "
                "the Pallas backend computes the value of one matrix, in "
                "rank windows, on one device or over a mesh (radic_det or "
                "radic_det_distributed with backend='pallas'); batched "
                "and gradient programs need C(n, m) < 2**31.  Use the jnp "
                "distributed grain mode for those.")
    else:
        if total > INT32_MAX and not jax.config.jax_enable_x64:
            raise OverflowError(
                f"C({n},{m}) = {total} exceeds int32; enable x64 or use "
                "repro.core.distributed (mode='grains').")
    return total


# ------------------------------------------------------------- rank windows
def window_count(total: int) -> int | None:
    """Rank windows of a B = 1 Pallas determinant over ``total`` ranks:
    the fewest whose longest (``plan_grains`` cuts evenly) holds at most
    :data:`WINDOW_RANKS`, or ``None`` where one int32 rank range holds
    them all and the plain value kernel runs."""
    if total <= WINDOW_RANKS:
        return None
    return -(-total // WINDOW_RANKS)


def window_slacks(q0: int, n: int, m: int) -> np.ndarray:
    """What the windowed kernel knows of the window starting at rank
    ``q0``: ``(n,)`` int32, entry ``v − 1`` for column v.

    Rank ``q0 + d`` walks the same path as ``S0 = unrank_py(q0)`` until
    the first column v that S0 places where ``d`` reaches the slack
    ``C(n − v, m − 1 − pos) − r_v``, ``r_v`` S0's remaining rank at v;
    there it skips v and goes on as an ordinary walk of the rank
    ``d − slack``.  A column S0 skips is skipped on the path too.  The
    entry is that slack, clipped at ``INT32_MAX`` (``d`` never reaches
    it), where S0 places v, and 0 where it skips v.  ``r_v`` follows
    from S0 by the hockey-stick sums of :func:`~repro.core.unrank.rank_py`.
    """
    out = np.zeros(n, np.int64)
    r, prev = q0, 0
    for i, c in enumerate(unrank_py(q0, n, m)):
        r -= comb(n - prev, m - i) - comb(n - c + 1, m - i)  # skipped
        out[c - 1] = min(comb(n - c, m - 1 - i) - r, INT32_MAX)
        prev = c
    return out.astype(np.int32)


@dataclass(frozen=True, eq=False)
class RankWindows:
    """Contiguous rank windows of one rank range: each window's start
    (a host int, any width), its length (at most :data:`WINDOW_RANKS`)
    and its :func:`window_slacks`, ``(W, n)`` int32."""

    starts: tuple
    lengths: tuple
    slacks: np.ndarray = field(repr=False)

    def __len__(self) -> int:
        return len(self.starts)


def plan_rank_windows(m: int, n: int, q_start: int, count: int, *,
                      grain: int | None = None) -> RankWindows:
    """Cut ranks ``[q_start, q_start + count)`` into the fewest windows of
    at most :data:`WINDOW_RANKS` ranks, ``plan_grains``' even cut, and
    unrank each window's start on the host with exact ints.  Runs in a
    ``det.plan.windows`` profiler span (metadata ``windows``,
    ``window_ranks``: the count and the longest; and ``grain``, the
    index of a mesh device's grain, where given)."""
    from .distributed import plan_grains  # lazy: distributed builds on us
    W = window_count(count) or 1
    meta = {} if grain is None else {"grain": grain}
    with jax.profiler.TraceAnnotation("det.plan.windows", windows=W,
                                      window_ranks=-(-count // W), **meta):
        starts, lengths = plan_grains(count, W)
        starts = tuple(q_start + q for q in starts)
        slacks = np.stack([window_slacks(q, n, m) if length
                           else np.zeros(n, np.int32)
                           for q, length in zip(starts, lengths)])
    return RankWindows(starts=starts, lengths=tuple(lengths), slacks=slacks)


def rank_table(n: int, m: int, *, backend: str = "jnp") -> jax.Array:
    """The Pascal table at the rank dtype the backend computes in.

    Always a *concrete* array: plans (and their tables) are LRU-cached
    and outlive any caller's trace, so materializing the table while
    tracing under an outer ``jax.jit`` would leak that trace's constant
    tracer into every later use of the cached plan."""
    if backend == "pallas":
        tdtype = np.int32
    else:
        tdtype = np.int64 if jax.config.jax_enable_x64 else np.int32
    with jax.ensure_compile_time_eval():
        return jnp.asarray(binom_table(n, m, dtype=tdtype))


def plan_statics(m: int, n: int, chunk: int, *, backend: str = "jnp"):
    """``(total, table, clamped chunk)`` — the per-shape state every flat
    jnp program binds.  One place, so traced / AOT / engine paths binding
    it are bit-identical by construction."""
    total = validate_rank_space(m, n, backend=backend)
    table = rank_table(n, m, backend=backend)
    return total, table, int(min(chunk, max(total, 1)))


# ------------------------------------------------------------------ plan key
class PlanKey(NamedTuple):
    """Everything that selects a distinct device program.

    A real tuple (``NamedTuple``), so a mesh-free key is *stable and
    serializable*: it pickles across process boundaries, hashes by
    value and round-trips through ``tuple(key)`` — the properties the
    multi-worker serving front relies on to route by plan family.  The
    routing projection itself ``(m, n, capacity, dtype, x64)`` lives in
    :func:`repro.launch.det_front.route_key`, NOT here: a family's
    capacity component is the *policy bound*, while this key's
    ``capacity`` is one batch's exact size — per-batch keys of one
    family must all land on the same worker, so deriving a routing key
    from an individual plan key would split families across the pool.
    """

    m: int
    n: int
    batched: bool
    capacity: int | None        # None → shape-polymorphic traced program
    dtype: str
    backend: str
    chunk: int                  # as requested (clamp is derived state)
    kahan: bool
    mesh: Any                   # jax.sharding.Mesh (hashable) or None
    axis_names: tuple | None
    batch_axis: str | None
    mode: str                   # mesh scalar only: "grains" | "flat"
    grains_per_device: int
    x64: bool                   # captured at plan time; flips re-plan


def _canonical_key_item(v):
    """Numpy scalars repr differently from the python values they equal
    (``np.int64(3)`` vs ``3`` under numpy >= 2), so a key built from an
    array's ``.shape`` member or decoded off the wire must hash like
    the plain-python key the ring was populated with."""
    if isinstance(v, np.bool_):
        return bool(v)
    if isinstance(v, np.integer):
        return int(v)
    if isinstance(v, np.floating):
        return float(v)
    if isinstance(v, np.str_):
        return str(v)
    if isinstance(v, tuple):
        return tuple(_canonical_key_item(x) for x in v)
    return v


def stable_key_hash(key) -> int:
    """Deterministic 64-bit hash of a (routing) key tuple.

    Builtin ``hash()`` is salted per process for strings
    (``PYTHONHASHSEED``), so it cannot place keys on a consistent-hash
    ring that must agree across processes and restarts.  This hash is a
    pure function of the key's ``repr`` — stable everywhere, invariant
    under numpy-scalar vs python-scalar components and therefore under
    a wire encode/decode round-trip — which is what makes the front's
    re-routing after a worker death deterministic.
    """
    data = repr(tuple(_canonical_key_item(v) for v in key)).encode("utf-8")
    return int.from_bytes(hashlib.blake2b(data, digest_size=8).digest(),
                          "big")


def kernel_rank_rows(key: PlanKey) -> int | None:
    """Rank rows per sublane group of the value kernel a plan runs: 8
    where each kernel call sees one matrix (scalar plans, on one device or
    per mesh device, and batched plans at capacity 1), 1 where it sees a
    stack, ``None`` where no kernel runs or the batch is left to the call
    (no capacity)."""
    if key.backend != "pallas" or key.m > key.n:
        return None
    batch = 1 if not key.batched else key.capacity
    if batch is None:
        return None
    if key.batched and key.mesh is not None and key.batch_axis:
        batch //= key.mesh.shape[key.batch_axis]
    from repro.kernels.radic_fused import rank_rows  # kernels import core
    return rank_rows(batch)


def kernel_gather(key: PlanKey) -> str | None:
    """How the plan's value kernel gathers its minors: ``"bf16x3"`` where
    it runs the rank-row layout (one bf16 pass over A's exact three-part
    split, ``kernels.common.rank_row_slabs``; windowed plans too),
    ``"f32_highest"`` where it gathers a stack at ``Precision.HIGHEST``
    (``common.minor_slabs``, as every gradient kernel does), ``None``
    where :func:`kernel_rank_rows` is ``None``."""
    rows = kernel_rank_rows(key)
    if rows is None:
        return None
    return "bf16x3" if rows > 1 else "f32_highest"


def kernel_walk(key: PlanKey) -> str | None:
    """How the plan's value kernel finds each lane's combination, as the
    kernel module answers for its :func:`kernel_rank_rows`
    (``kernels.radic_fused.rank_walk``): ``"successor"``, ``"unrank"``
    or ``None``."""
    from repro.kernels.radic_fused import rank_walk  # kernels import core
    return rank_walk(kernel_rank_rows(key))


def _mesh_devices(key: PlanKey) -> int:
    """Devices a mesh plan cuts the rank space over: the product of the
    sizes of its axes (every axis where the key names none)."""
    axes = key.axis_names or key.mesh.axis_names
    return math.prod(key.mesh.shape[a] for a in axes)


def kernel_windows(key: PlanKey) -> int | None:
    """Rank windows of the plan's value program: a scalar Pallas plan past
    :data:`WINDOW_RANKS` runs :func:`window_count` windows on one device,
    and over a mesh of D devices D · W_d, each device's grain
    (``plan_grains``) in W_d windows, the count of its longest grain;
    ``None`` for every other plan."""
    if key.backend != "pallas" or key.batched or key.m > key.n:
        return None
    total = comb(key.n, key.m)
    if key.mesh is None or window_count(total) is None:
        return window_count(total)
    D = _mesh_devices(key)
    return D * (window_count(-(-total // D)) or 1)


def _make_differentiable(primal: Callable, grad_fn: Callable) -> Callable:
    """Wrap a plan's *traced* primal closure in a ``jax.custom_vjp`` whose
    backward pass is the plan's cofactor-form grad program.

    Eager forward calls execute ``primal`` directly (jax's eval trace
    calls the wrapped function without tracing it), so the value path is
    unchanged; only under differentiation do ``fwd``/``bwd`` trace.  The
    primal passed here must be the traced-closure program, never an
    AOT-compiled executable — compiled executables reject tracers, and
    ``jax.jit(jax.grad(...))`` traces the bwd rule too, which is why the
    AOT grad executable lives separately on ``DetPlan.grad_executable``
    for the serving tier's concrete-batch dispatch.
    """

    @jax.custom_vjp
    def det_fn(A):
        return primal(A)

    def det_fwd(A):
        return primal(A), A

    def det_bwd(A, ct):
        return (grad_fn(A, ct),)

    det_fn.defvjp(det_fwd, det_bwd)
    return det_fn


def _values_only(primal: Callable, why: str) -> Callable:
    """``primal`` behind a ``jax.custom_vjp`` whose forward rule raises:
    ``jax.grad`` of a plan with no gradient program fails at once, before
    any kernel runs, and never returns a wrong value."""

    @jax.custom_vjp
    def det_fn(A):
        return primal(A)

    def det_fwd(A):
        raise OverflowError(why)

    def det_bwd(res, ct):
        raise OverflowError(why)

    det_fn.defvjp(det_fwd, det_bwd)
    return det_fn


def _zeros_grad(A: jax.Array, ct: jax.Array) -> jax.Array:
    """m > n ⇒ det ≡ 0 ⇒ the pullback is identically zero."""
    del ct
    return jnp.zeros_like(jnp.asarray(A))


# jitted degenerate programs: m > n ⇒ det = 0 by the paper's definition,
# but normalized as a *device* program so every configuration (backend,
# mesh or not) hands back a committed jax.Array like the real paths do.
@jax.jit
def _zeros_scalar(A: jax.Array) -> jax.Array:
    return jnp.zeros((), A.dtype)


@jax.jit
def _zeros_batched(As: jax.Array) -> jax.Array:
    return jnp.zeros((As.shape[0],), As.dtype)


@dataclass(frozen=True, eq=False)
class DetPlan:
    """Immutable per-shape compilation artifact: validated statics plus
    the executable.  Calling the plan runs the executable; everything
    host-side (validation, table build, grain unranking, AOT lowering)
    happened at plan time.  ``eq=False``: plans compare by identity —
    the generated value-eq would hit the device ``table`` array (ambiguous
    truth value / unhashable); the engine cache already guarantees one
    plan per key."""

    key: PlanKey
    total: int                  # C(n, m)
    chunk: int                  # clamped to the rank space
    degenerate: bool            # m > n: executable is the zeros program
    lowered: bool               # True when AOT-lowered at a capacity
    table: Any = field(repr=False)          # device Pascal table or None
    executable: Callable = field(repr=False)
    # Second plan-time artifact (DESIGN_GRAD.md): the cofactor-form VJP
    # over the same rank walk.  ``grad_executable(A, ct) -> ∂/∂A`` is the
    # serving-grade program (AOT-lowered at capacity where the forward
    # is); ``differentiable`` is the custom_vjp-wrapped traced closure
    # behind ``jax.grad(radic_det)`` / ``jax.grad(radic_det_batched)``.
    grad_executable: Callable = field(repr=False)
    differentiable: Callable = field(repr=False)
    # Rank windows of the value program (:func:`kernel_windows`): W for a
    # scalar Pallas plan past WINDOW_RANKS ranks (D · W_d over a mesh),
    # None for the others.
    windows: int | None = None

    @property
    def m(self) -> int:
        return self.key.m

    @property
    def n(self) -> int:
        return self.key.n

    @property
    def capacity(self) -> int | None:
        return self.key.capacity

    @property
    def backend(self) -> str:
        return self.key.backend

    @property
    def rank_rows(self) -> int | None:
        """The engagement record of the value kernel's layout: 8 rank
        rows per sublane group, 1, or ``None`` (:func:`kernel_rank_rows`)."""
        return kernel_rank_rows(self.key)

    @property
    def gather(self) -> str | None:
        """The engagement record of the value kernel's gather:
        ``"bf16x3"``, ``"f32_highest"`` or ``None`` (:func:`kernel_gather`)."""
        return kernel_gather(self.key)

    @property
    def walk(self) -> str | None:
        """The engagement record of the value kernel's rank walk:
        ``"successor"``, ``"unrank"`` or ``None`` (:func:`kernel_walk`)."""
        return kernel_walk(self.key)

    def __call__(self, A: jax.Array) -> jax.Array:
        return self.executable(A)

    def grad(self, A: jax.Array, ct) -> jax.Array:
        """Pull the cotangent(s) back through the determinant: scalar
        plans take ``A (m, n)`` and a scalar ``ct``; batched plans take
        ``As (B, m, n)`` and ``cts (B,)`` and return ``(B, m, n)``."""
        return self.grad_executable(A, ct)


# -------------------------------------------------------------- the engine
class DetEngine:
    """Plan once, execute many — with an LRU-bounded executable cache.

    The cache bound exists for long-tail shape traffic (the serving
    tier's open problem): an unbounded per-(shape, capacity) executable
    map grows without limit under adversarial or merely diverse request
    streams.  Eviction is safe because plans are pure functions of their
    key — an evicted shape re-plans and reproduces bit-identical results.

    Thread-safe: lookups and inserts are locked; compilation happens
    outside the lock, and a racing duplicate build keeps the first
    inserted plan so every caller converges on one executable.
    """

    # reprolint lock-discipline registry (see DESIGN_LINT.md): the LRU
    # map and its counters are shared by every dispatcher thread.
    _GUARDED_BY = {
        "_plans": ("_lock",),
        "_hits": ("_lock",),
        "_misses": ("_lock",),
        "_evictions": ("_lock",),
        "_store_hits": ("_lock",),
        "_store_misses": ("_lock",),
        "_windows": ("_lock",),
        "_grid_ranks": ("_lock",),
    }

    def __init__(self, max_plans: int = 128,
                 persist_dir: str | None = None):
        if max_plans < 1:
            raise ValueError("max_plans must be >= 1")
        self.max_plans = max_plans
        self._plans: OrderedDict[PlanKey, DetPlan] = OrderedDict()
        self._lock = threading.Lock()
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        self._store_hits = 0
        self._store_misses = 0
        self._windows = 0
        self._grid_ranks = 0
        # Optional durable plan store (DESIGN_PERSIST.md): consulted on
        # cache misses, written back asynchronously after fresh builds.
        self.store = None
        if persist_dir is not None:
            from repro.checkpoint.plan_store import PlanStore
            self.store = PlanStore(persist_dir, env={
                "jax": jax.__version__,
                "backend": jax.default_backend(),
            })
            # The store dir also houses an XLA persistent compilation
            # cache: on jax legs where blob reload is unsafe (the default
            # — see the compat export seam) this is what makes a warm
            # start skip the XLA compile, not just the store lookup.
            _compat.enable_compilation_cache(
                os.path.join(persist_dir, "xla-cache"))

    # ------------------------------------------------------------- planning
    def plan(self, m: int, n: int, *, batched: bool = True,
             capacity: int | None = None, dtype=np.float32,
             chunk: int = 2048, backend: Backend = "jnp",
             kahan: bool = False, mesh=None,
             axis_names: Sequence[str] | None = None,
             batch_axis: str | None = None,
             mode: Literal["grains", "flat"] = "grains",
             grains_per_device: int = 1) -> DetPlan:
        """Return the cached plan for this configuration, building it if
        absent.  All validation happens here, before backend dispatch.
        A miss restores or builds inside a ``det.plan.build`` profiler
        span (the mesh backend's grain planning and a windowed plan's
        ``det.plan.windows`` fall inside it), whose metadata ``rank_rows``,
        ``gather``, ``walk`` and ``windows`` are the plan's
        :attr:`DetPlan.rank_rows`, :attr:`DetPlan.gather`,
        :attr:`DetPlan.walk` and :attr:`DetPlan.windows`."""
        if backend not in ("jnp", "pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        if kahan and batched:
            raise ValueError("kahan compensation is flat-mode only")
        if capacity is not None and not batched:
            raise ValueError("capacity is a batched-plan parameter")
        key = PlanKey(
            m=int(m), n=int(n), batched=batched,
            capacity=None if capacity is None else int(capacity),
            dtype=np.dtype(dtype).name, backend=backend, chunk=int(chunk),
            kahan=kahan, mesh=mesh,
            axis_names=None if axis_names is None else tuple(axis_names),
            batch_axis=batch_axis, mode=mode,
            grains_per_device=int(grains_per_device),
            x64=bool(jax.config.jax_enable_x64))
        with self._lock:
            plan = self._plans.get(key)
            if plan is not None:
                self._plans.move_to_end(key)
                self._hits += 1
                return plan
        with jax.profiler.TraceAnnotation("det.plan.build",
                                          rank_rows=kernel_rank_rows(key),
                                          gather=kernel_gather(key),
                                          walk=kernel_walk(key),
                                          windows=kernel_windows(key)):
            built = None
            consulted = self.store is not None and self._persistable(key)
            if consulted:
                built = self._restore_from_store(key)
                with self._lock:
                    if built is not None:
                        self._store_hits += 1
                    else:
                        self._store_misses += 1
            if built is None:
                built = self._build(key)
                if consulted:
                    self._persist_async(key, built)
        with self._lock:
            plan = self._plans.get(key)
            if plan is not None:  # racing build: first insert wins
                self._plans.move_to_end(key)
                self._hits += 1
                return plan
            self._misses += 1
            self._plans[key] = built
            while len(self._plans) > self.max_plans:
                self._plans.popitem(last=False)
                self._evictions += 1
        return built

    # ------------------------------------------------------------ execution
    def det(self, A: jax.Array, *, chunk: int = 2048, kahan: bool = False,
            backend: Backend = "jnp", **mesh_kw) -> jax.Array:
        """Scalar convenience: plan for ``A.shape`` and execute."""
        A = jnp.asarray(A)
        m, n = A.shape
        return self.plan(m, n, batched=False, dtype=A.dtype, chunk=chunk,
                         kahan=kahan, backend=backend, **mesh_kw)(A)

    def det_batched(self, As: jax.Array, *, chunk: int = 2048,
                    backend: Backend = "jnp", **mesh_kw) -> jax.Array:
        """Batched convenience: plan for ``As.shape[1:]`` and execute."""
        As = jnp.asarray(As)
        _, m, n = As.shape
        return self.plan(m, n, batched=True, dtype=As.dtype, chunk=chunk,
                         backend=backend, **mesh_kw)(As)

    # ------------------------------------------------------------- the cache
    def cache_info(self) -> dict:
        """Plan-cache counters; ``windows`` counts the rank windows that
        windowed plans have dispatched (W per call, D · W_d over a mesh),
        ``grid_ranks`` the rank slots their grids covered: per call W ·
        steps · 8 · tile (``kernels.ops.window_grid``), real ranks and
        padding alike."""
        with self._lock:
            return {"size": len(self._plans), "max_plans": self.max_plans,
                    "hits": self._hits, "misses": self._misses,
                    "evictions": self._evictions,
                    "store_hits": self._store_hits,
                    "store_misses": self._store_misses,
                    "windows": self._windows,
                    "grid_ranks": self._grid_ranks}

    def cached_keys(self) -> list[PlanKey]:
        """LRU order, oldest first (introspection/tests)."""
        with self._lock:
            return list(self._plans)

    def clear(self):
        with self._lock:
            self._plans.clear()

    # ------------------------------------------------- persistence (store)
    #
    # The durable plan store (DESIGN_PERSIST.md) is consulted on cache
    # misses and written back to after fresh builds.  A store *hit*
    # means the store held a valid record for this exact key — when the
    # record carries serialized AOT executables (jax.export leg) the
    # compile is skipped entirely; a metadata-only record still re-lowers
    # from statics, which is what prefill needs (pay the compile at join
    # time, not on the first request).  Mesh plans are never persisted:
    # a Mesh is a live device object with no cross-process identity.

    @staticmethod
    def _persistable(key: PlanKey) -> bool:
        return key.mesh is None

    @staticmethod
    def _key_meta(key: PlanKey) -> dict:
        """Mesh-free plain-JSON form of a PlanKey — the store's record
        of *what* was planned, sufficient to re-plan it elsewhere."""
        return {"m": key.m, "n": key.n, "batched": key.batched,
                "capacity": key.capacity, "dtype": key.dtype,
                "backend": key.backend, "chunk": key.chunk,
                "kahan": key.kahan, "mode": key.mode,
                "grains_per_device": key.grains_per_device, "x64": key.x64}

    @staticmethod
    def _plan_kwargs(meta) -> dict | None:
        """Decode a stored/wire key meta back into ``plan()`` kwargs;
        None if malformed or its x64 stamp disagrees with this process
        (x64 flips select different programs — never mix them)."""
        if not isinstance(meta, dict):
            return None
        try:
            if bool(meta.get("x64", False)) != bool(
                    jax.config.jax_enable_x64):
                return None
            cap = meta.get("capacity")
            return dict(
                m=int(meta["m"]), n=int(meta["n"]),
                batched=bool(meta.get("batched", True)),
                capacity=None if cap is None else int(cap),
                dtype=str(meta.get("dtype", "float32")),
                chunk=int(meta.get("chunk", 2048)),
                backend=str(meta.get("backend", "jnp")),
                kahan=bool(meta.get("kahan", False)),
                mode=str(meta.get("mode", "grains")),
                grains_per_device=int(meta.get("grains_per_device", 1)))
        except (KeyError, TypeError, ValueError):
            return None

    def _restore_from_store(self, key: PlanKey) -> DetPlan | None:
        rec = self.store.get(stable_key_hash(key))
        if rec is None:
            return None
        meta, blobs = rec
        if meta.get("key") != self._key_meta(key):
            return None     # hash collision or corrupt entry: miss
        plan = self._plan_from_blobs(key, blobs) if blobs else None
        # metadata-only record (no export on this jax, or a non-AOT
        # plan): still a store hit — re-lower from the cached statics
        return plan if plan is not None else self._build(key)

    def _plan_from_blobs(self, key: PlanKey, blobs: dict) -> DetPlan | None:
        """Rebuild an AOT batched plan from serialized executables.

        Only jnp/batched/capacity plans ever carry blobs (they are the
        only ``lowered=True`` programs).  Any deserialization failure
        degrades to None — caller re-lowers from statics instead.
        """
        if (key.backend != "jnp" or not key.batched
                or key.capacity is None or key.m > key.n):
            return None
        fwd_b, grad_b = blobs.get("fwd"), blobs.get("grad")
        if fwd_b is None or grad_b is None:
            return None
        exe = _compat.deserialize_exported(fwd_b)
        gexe = _compat.deserialize_exported(grad_b)
        if exe is None or gexe is None:
            return None
        total, table, chunk = plan_statics(key.m, key.n, key.chunk)
        execute_traced, grad_traced = self._traced_batched(
            key, table, total, chunk)
        execute = functools.partial(lambda As, _e, _t: _e(As, _t),
                                    _e=exe, _t=table)
        grad_execute = functools.partial(
            lambda As, cts, _e, _t: _e(As, cts, _t), _e=gexe, _t=table)
        return DetPlan(key=key, total=total, chunk=chunk, degenerate=False,
                       lowered=True, table=table, executable=execute,
                       grad_executable=grad_execute,
                       differentiable=_make_differentiable(
                           execute_traced, grad_traced))

    def _persist_async(self, key: PlanKey, plan: DetPlan) -> None:
        """Enqueue a store write-back for a freshly built plan.

        Export serialization is deferred as callables evaluated on the
        store's writer thread — the dispatch path never pays it.
        """
        meta = {"key": self._key_meta(key), "total": plan.total,
                "chunk": plan.chunk, "lowered": plan.lowered,
                "degenerate": plan.degenerate}
        blobs = {}
        if plan.lowered and not plan.degenerate:
            batch_s = jax.ShapeDtypeStruct(
                (key.capacity, key.m, key.n), np.dtype(key.dtype))
            ct_s = jax.ShapeDtypeStruct((key.capacity,),
                                        np.dtype(key.dtype))
            fn = (_radic_det_batched_flat_donated if _donation_supported()
                  else _radic_det_batched_flat)
            blobs = {
                "fwd": functools.partial(
                    _compat.serialize_lowered, fn, batch_s, plan.table,
                    plan.total, plan.chunk),
                "grad": functools.partial(
                    _compat.serialize_lowered, _radic_det_batched_grad_flat,
                    batch_s, ct_s, plan.table, plan.total, plan.chunk),
            }
        self.store.put_async(stable_key_hash(key), meta, blobs)

    def flush_store(self) -> None:
        """Block until pending store write-backs land (tests/shutdown)."""
        if self.store is not None:
            self.store.flush()

    def prefill(self, families=None) -> int:
        """Warm the plan cache — store first, compile second.

        ``families``: iterable of key-meta dicts (e.g. decoded from a
        join handshake's prefill list); with None, every family the
        store holds is planned.  Malformed entries, x64 mismatches and
        plan failures are skipped.  Returns the number of entries
        successfully planned (cache hits included — already warm counts
        as warm).
        """
        if families is None:
            if self.store is None:
                return 0
            families = [m.get("key") for m in self.store.families()]
        warmed = 0
        for meta in families:
            kw = self._plan_kwargs(meta)
            if kw is None:
                continue
            try:
                self.plan(**kw)
                warmed += 1
            except Exception:   # noqa: BLE001 — prefill is best-effort
                continue
        return warmed

    # ------------------------------------------------------------- builders
    def _build(self, key: PlanKey) -> DetPlan:
        m, n = key.m, key.n
        total = validate_rank_space(
            m, n, backend=key.backend,
            mesh_grains=key.mesh is not None and not key.batched
            and key.mode == "grains" and key.backend == "jnp",
            windowed=kernel_windows(key) is not None)
        if m > n:
            exe = _zeros_batched if key.batched else _zeros_scalar
            def execute(A, _exe=exe):
                return _exe(jnp.asarray(A))
            # det ≡ 0: the jitted zeros program is trivially
            # differentiable, so it is its own custom_vjp-free
            # `differentiable` path.
            return DetPlan(key=key, total=total, chunk=0, degenerate=True,
                           lowered=False, table=None, executable=execute,
                           grad_executable=_zeros_grad,
                           differentiable=execute)
        if key.mesh is not None:
            return self._build_mesh(key, total)
        if key.backend == "pallas":
            return self._build_pallas(key, total)
        return self._build_jnp(key, total)

    @staticmethod
    def _traced_batched(key: PlanKey, table, total: int, chunk: int):
        """The shape-checked traced closures every batched jnp plan
        carries (shared by fresh builds and store restores, so a
        restored plan's ``differentiable`` path is the same program)."""
        m, n = key.m, key.n

        def execute_traced(As, _t=table, _total=total, _c=chunk, _m=m, _n=n):
            As = jnp.asarray(As)
            if As.ndim != 3 or As.shape[1:] != (_m, _n):
                raise ValueError(
                    f"expected (B, {_m}, {_n}), got {As.shape}")
            if As.shape[0] == 0:
                return jnp.zeros((0,), As.dtype)
            return _radic_det_batched_flat(As, _t, _total, _c)

        def grad_traced(As, cts, _t=table, _total=total, _c=chunk):
            As = jnp.asarray(As)
            return _radic_det_batched_grad_flat(
                As, jnp.asarray(cts, As.dtype), _t, _total, _c)

        return execute_traced, grad_traced

    def _build_jnp(self, key: PlanKey, total: int) -> DetPlan:
        m, n = key.m, key.n
        _, table, chunk = plan_statics(m, n, key.chunk)
        if not key.batched:
            def execute(A, _t=table, _total=total, _c=chunk, _k=key.kahan):
                return _radic_det_flat(jnp.asarray(A), _t, _total, _c, _k)

            # The backward walk never compensates: d(kahan_sum)/dA equals
            # d(plain_sum)/dA exactly, the compensation terms are
            # arithmetic identities of the forward accumulation order.
            def grad_execute(A, ct, _t=table, _total=total, _c=chunk):
                A = jnp.asarray(A)
                return _radic_det_grad_flat(
                    A, jnp.asarray(ct, A.dtype), _t, _total, _c)
            return DetPlan(key=key, total=total, chunk=chunk,
                           degenerate=False, lowered=False, table=table,
                           executable=execute, grad_executable=grad_execute,
                           differentiable=_make_differentiable(
                               execute, grad_execute))

        execute_traced, grad_traced = self._traced_batched(
            key, table, total, chunk)
        execute, grad_execute, lowered = execute_traced, grad_traced, False
        if key.capacity is not None:
            # AOT-lower the *same* jitted programs the traced path enters
            # — the identical XLA computations, so results are
            # bit-identical — paying the per-dispatch python once here.
            # Where the backend honors it, the staged batch buffer is
            # donated (it is dead after the dispatch): same program,
            # same results, one less live buffer per inflight batch.
            # The grad program does not donate: its (B, m, n) primal
            # input is also its residual, read throughout the walk.
            fn = (_radic_det_batched_flat_donated if _donation_supported()
                  else _radic_det_batched_flat)
            batch_s = jax.ShapeDtypeStruct((key.capacity, m, n),
                                           np.dtype(key.dtype))
            ct_s = jax.ShapeDtypeStruct((key.capacity,), np.dtype(key.dtype))
            try:
                exe = fn.lower(batch_s, table, total, chunk).compile()
                execute = functools.partial(lambda As, _e, _t: _e(As, _t),
                                            _e=exe, _t=table)
                gexe = _radic_det_batched_grad_flat.lower(
                    batch_s, ct_s, table, total, chunk).compile()
                grad_execute = functools.partial(
                    lambda As, cts, _e, _t: _e(As, cts, _t), _e=gexe,
                    _t=table)
                lowered = True
            except Exception:  # noqa: BLE001 — AOT is an optimization only
                # ...on the CPU test backend.  On an accelerator a failed
                # compile is a fault of the program, and serving the
                # traced program instead would hide it.
                if jax.default_backend() != "cpu":
                    raise
                execute, grad_execute = execute_traced, grad_traced
        return DetPlan(key=key, total=total, chunk=chunk, degenerate=False,
                       lowered=lowered, table=table, executable=execute,
                       grad_executable=grad_execute,
                       differentiable=_make_differentiable(
                           execute_traced, grad_traced))

    def _build_pallas(self, key: PlanKey, total: int) -> DetPlan:
        from repro.kernels import ops  # lazy: kernels depend on core
        if kernel_windows(key) is not None:
            return self._build_windowed(key, total, ops)
        fn = (ops.radic_det_batched_pallas if key.batched
              else ops.radic_det_pallas)
        gfn = (ops.radic_det_batched_grad_pallas if key.batched
               else ops.radic_det_grad_pallas)
        execute = functools.partial(fn, q_start=0, count=total)
        grad_execute = functools.partial(gfn, q_start=0, count=total)
        return DetPlan(key=key, total=total,
                       chunk=int(min(key.chunk, max(total, 1))),
                       degenerate=False, lowered=False, table=None,
                       executable=execute, grad_executable=grad_execute,
                       differentiable=_make_differentiable(
                           execute, grad_execute))

    def _build_windowed(self, key: PlanKey, total: int, ops) -> DetPlan:
        """One matrix past WINDOW_RANKS ranks: the windows are planned
        here, once, and every call runs them all in one program of the
        windowed kernel."""
        windows = plan_rank_windows(key.m, key.n, 0, total)
        return self._windowed_plan(key, total, windows, functools.partial(
            ops.radic_det_pallas, q_start=0, count=total, windows=windows))

    def _windowed_plan(self, key: PlanKey, total: int, windows,
                       run: Callable) -> DetPlan:
        """The plan of a windowed program ``run`` over ``windows``, on one
        device or over a mesh: each call counts its windows and its grid's
        rank slots.  Values only: no gradient program is built."""
        from repro.kernels.ops import window_grid  # kernels import core
        _, slots = window_grid(windows.lengths)

        def execute(A, _run=run, _w=len(windows), _s=slots):
            with self._lock:
                self._windows += _w
                self._grid_ranks += _s
            return _run(A)

        why = (f"C({key.n},{key.m}) = {total} exceeds int32: the windowed "
               "Pallas plan computes values only; gradients past int32 "
               "are not supported")

        def grad_execute(A, ct):
            raise OverflowError(why)

        return DetPlan(key=key, total=total,
                       chunk=int(min(key.chunk, total)), degenerate=False,
                       lowered=False, table=None, executable=execute,
                       grad_executable=grad_execute,
                       differentiable=_values_only(execute, why),
                       windows=len(windows))

    def _build_mesh(self, key: PlanKey, total: int) -> DetPlan:
        from .distributed import (make_batched_distributed_evaluator,
                                  make_batched_distributed_grad_evaluator,
                                  make_distributed_evaluator,
                                  plan_grain_windows)
        if not key.batched and kernel_windows(key) is not None:
            windows = plan_grain_windows(key.m, key.n, _mesh_devices(key))
            return self._windowed_plan(
                key, total, windows, make_distributed_evaluator(
                    key.m, key.n, mesh=key.mesh, axis_names=key.axis_names,
                    backend=key.backend, windows=windows))
        if key.batched:
            execute = make_batched_distributed_evaluator(
                key.m, key.n, mesh=key.mesh, axis_names=key.axis_names,
                batch_axis=key.batch_axis, chunk=key.chunk,
                backend=key.backend)
            grad_execute = make_batched_distributed_grad_evaluator(
                key.m, key.n, mesh=key.mesh, axis_names=key.axis_names,
                batch_axis=key.batch_axis, chunk=key.chunk,
                backend=key.backend)
        else:
            execute = make_distributed_evaluator(
                key.m, key.n, mesh=key.mesh, axis_names=key.axis_names,
                grains_per_device=key.grains_per_device, mode=key.mode,
                chunk=key.chunk, backend=key.backend)

            # Scalar mesh plans (grains/flat) serve the interactive
            # single-matrix path; gradient traffic is batched, so the
            # pullback falls back to the single-device flat program.
            # plan_statics re-runs the width guard at first use: a
            # bigint-only grains rank space has no single-device grad.
            def grad_execute(A, ct, _m=key.m, _n=key.n, _chunk=key.chunk):
                total_, table_, chunk_ = plan_statics(_m, _n, _chunk)
                A = jnp.asarray(A)
                return _radic_det_grad_flat(
                    A, jnp.asarray(ct, A.dtype), table_, total_, chunk_)
        return DetPlan(key=key, total=total,
                       chunk=int(min(key.chunk, max(total, 1))),
                       degenerate=False, lowered=False, table=None,
                       executable=execute, grad_executable=grad_execute,
                       differentiable=_make_differentiable(
                           execute, grad_execute))


# ------------------------------------------------------------ default engine
_default_engine: DetEngine | None = None
_default_lock = threading.Lock()


def default_engine() -> DetEngine:
    """The process-wide engine behind the module-level entry points
    (``radic_det``, ``radic_det_batched``, …)."""
    global _default_engine
    with _default_lock:
        if _default_engine is None:
            _default_engine = DetEngine()
        return _default_engine


def set_default_engine(engine: DetEngine | None) -> None:
    """Swap (or with ``None``, reset) the process-wide engine — tests and
    embedders that want their own cache bound."""
    global _default_engine
    with _default_lock:
        _default_engine = engine
