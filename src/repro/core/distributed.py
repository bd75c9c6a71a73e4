"""Mesh-distributed Radic determinant — the paper's granularity scheme.

Section 5 of the paper: with ``k`` processors, the rank space
``[0, C(n,m))`` is cut into ``k`` contiguous grains; each processor unranks
its grain start once (combinatorial addition) and then walks successors
inside the grain.  Here a "processor" is a mesh device; the tree-sum of the
PRAM CREW analysis becomes a single ``psum`` over the mesh axes.

* ``"grains"`` (default) — grain starts are unranked on the **host with
  exact bigints** (no integer-width limit); devices enumerate successors
  lock-step across their local grains via a vectorized ``scan``: the
  paper's per-processor loop.
* ``"flat"`` — every rank is unranked independently on-device (the
  PRAM-CRCW shape).  Requires ``C(n,m) < 2**31`` (int32 ranks).

With ``backend="pallas"`` both modes run the fused kernel on a device's
grains as one rank range ``(q_start, count)``; past ``WINDOW_RANKS``
ranks, as that range's rank windows (:func:`plan_grain_windows`).

This module is the engine's mesh backend (DESIGN_ENGINE.md): the
``make_*_evaluator`` makers bind the plan-time half (validation, grain
planning with host-bigint unranking, tables, the ``shard_map``-built
worker) once per shape; ``radic_det*_distributed`` route through
:class:`repro.core.engine.DetEngine`.  All ``shard_map`` use goes through
:mod:`repro.parallel.compat`.

Straggler mitigation: ``grains_per_device > 1`` oversubscribes grains so a
slow device's tail work can be re-executed (``repro.runtime.stragglers``);
grain partials are keyed by grain id, so the reduction is idempotent.
"""

from __future__ import annotations

import functools
import math
from typing import Literal, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel.compat import psum_scalar, pvary, shard_map

from .engine import (RankWindows, plan_rank_windows, rank_table,
                     validate_rank_space, window_count)
from .pascal import INT32_MAX, comb
from .radic import signed_minor_sum, signed_minor_sum_batched
from .unrank import successor_jnp, unrank_jnp, unrank_py

__all__ = ["radic_det_distributed", "radic_det_batched_distributed",
           "make_distributed_evaluator", "make_batched_distributed_evaluator",
           "make_batched_distributed_grad_evaluator", "plan_grains",
           "plan_grain_windows"]


def plan_grains(total: int, num_grains: int):
    """Contiguous grain bounds: ``num_grains`` slices covering [0, total)."""
    bounds = [total * g // num_grains for g in range(num_grains + 1)]
    starts = bounds[:-1]
    lengths = [b - a for a, b in zip(bounds[:-1], bounds[1:])]
    return starts, lengths


def _default_mesh() -> Mesh:
    devs = np.array(jax.devices())
    return Mesh(devs.reshape(len(devs)), ("workers",))


# ----------------------------------------------------------- plan-time makers
def make_distributed_evaluator(
    m: int,
    n: int,
    *,
    mesh: Mesh,
    axis_names: Sequence[str] | None = None,
    grains_per_device: int = 1,
    mode: Literal["grains", "flat"] = "grains",
    chunk: int = 1024,
    backend: Literal["jnp", "pallas"] = "jnp",
    windows: RankWindows | None = None,
):
    """Bind the host-side half of a mesh evaluation once for one (m, n).

    Grain planning (with the host-bigint grain-start unranking, the
    expensive part for astronomical C(n, m)), the Pascal table and the
    ``shard_map``-built worker are built here; the returned
    ``evaluate(A: (m, n)) -> scalar`` only enters device code: ``A``
    replicated, the rank space sharded, the result a replicated scalar.
    m > n is normalized by the engine first.  Past ``WINDOW_RANKS`` ranks
    the Pallas backend runs ``windows`` (:func:`plan_grain_windows`, which
    a plan computes once; without it they are planned here).
    """
    axes = tuple(axis_names if axis_names is not None else mesh.axis_names)
    D = math.prod(mesh.shape[a] for a in axes)
    # Pallas: a device's grains are one contiguous rank range, (q_start,
    # count) within int32, the grain's rank windows past WINDOW_RANKS.
    kernel = mode == "flat" or backend == "pallas"
    total = validate_rank_space(m, n, backend=backend, mesh_grains=not kernel,
                                windowed=backend == "pallas")
    if backend == "pallas" and window_count(total) is not None:
        return _make_windowed(m, n, mesh, axes, windows if windows is not None
                              else plan_grain_windows(m, n, D))
    if kernel:
        return _make_flat(m, n, mesh, axes, D, total, chunk, backend)
    G = D * grains_per_device
    if total < G:  # degenerate: fewer subsets than grains
        G = D  # keep one grain per device, some empty
    starts_q, lengths = plan_grains(total, G)
    starts = np.array([unrank_py(q, n, m) if l > 0 else [1] * m
                       for q, l in zip(starts_q, lengths)], dtype=np.int32)
    max_len = max(lengths) if lengths else 0
    lengths = np.array(lengths, dtype=np.int64 if max(lengths, default=0)
                       > INT32_MAX else np.int32)

    spec_g = P(axes)
    rep = P()

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(rep, spec_g, spec_g), out_specs=rep)
    def worker(A_rep, starts_loc, len_loc):
        # starts_loc: (F, m) — F local grains, walked in lock-step.
        def body(carry, _):
            combos, step, acc = carry
            valid = step < len_loc
            part = signed_minor_sum(A_rep, combos, valid)
            combos = successor_jnp(combos, n)
            return (combos, step + 1, acc + part), None

        init = (starts_loc, jnp.zeros_like(len_loc),
                pvary(jnp.zeros((), A_rep.dtype), axes))
        (_, _, acc), _ = jax.lax.scan(body, init, None, length=max_len)
        return psum_scalar(acc, axes)

    starts_a = jnp.asarray(starts)
    lengths_a = jnp.asarray(lengths)

    def evaluate(A: jax.Array) -> jax.Array:
        return worker(jnp.asarray(A), starts_a, lengths_a)

    return evaluate


def _make_flat(m, n, mesh, axes, D, total, chunk, backend):
    """PRAM-CRCW shape: every rank unranked on-device, D contiguous shards.

    The caller (``make_distributed_evaluator``) has already run the
    int32/x64 width guards via :func:`validate_rank_space`.
    """
    table = rank_table(n, m)  # int64 under x64, int32 otherwise
    starts_q, lengths = plan_grains(total, D)
    tdtype = table.dtype
    starts_q = jnp.asarray(np.array(starts_q, dtype=tdtype))
    lengths_a = jnp.asarray(np.array(lengths, dtype=tdtype))
    max_len = max(lengths)
    chunk = int(min(chunk, max(max_len, 1)))
    num_chunks = -(-max_len // chunk)

    # check_vma=False: pallas_call outputs don't carry vma metadata yet
    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, check_vma=False,
        in_specs=(P(), P(), P(axes), P(axes)), out_specs=P())
    def worker(A_rep, tab, q0, cnt):
        q0 = q0[0]
        cnt = cnt[0]
        if backend == "pallas":
            from repro.kernels import ops
            acc = ops.radic_partial_pallas(A_rep, tab, q0, cnt,
                                           num_chunks * chunk)
        else:
            idx = jnp.arange(chunk, dtype=tab.dtype)

            def body(c, acc):
                qs = q0 + c.astype(tab.dtype) * chunk + idx
                valid = qs < q0 + cnt
                combos = unrank_jnp(jnp.where(valid, qs, 0), n, m, tab)
                return acc + signed_minor_sum(A_rep, combos, valid)

            acc = jax.lax.fori_loop(0, num_chunks, body,
                                    pvary(jnp.zeros((), A_rep.dtype), axes))
        return psum_scalar(acc, axes)

    def evaluate(A: jax.Array) -> jax.Array:
        return worker(jnp.asarray(A), table, starts_q, lengths_a)

    return evaluate


def plan_grain_windows(m: int, n: int, devices: int) -> RankWindows:
    """The rank windows of a Pallas mesh plan past ``WINDOW_RANKS`` ranks.

    :func:`plan_grains` cuts ``[0, C(n, m))`` into one grain per device,
    and :func:`~repro.core.engine.plan_rank_windows` cuts each grain into
    its own windows, each start unranked on the host with exact ints (a
    ``det.plan.windows`` span per grain, metadata ``grain``).  A grain
    with fewer windows than the most, W_d, is padded with empty windows
    (no ranks, zero slacks), so window ``d·W_d + w`` is window w of
    device d's grain: D·W_d windows in all, grain-major.
    """
    starts_q, lengths = plan_grains(comb(n, m), devices)
    grains = [plan_rank_windows(m, n, q, count, grain=g)
              for g, (q, count) in enumerate(zip(starts_q, lengths))]
    W = max(len(w) for w in grains)
    starts, counts, slacks = [], [], []
    for w in grains:
        pad = W - len(w)
        starts += list(w.starts) + [w.starts[-1] + w.lengths[-1]] * pad
        counts += list(w.lengths) + [0] * pad
        slacks += [w.slacks, np.zeros((pad, n), np.int32)]
    return RankWindows(starts=tuple(starts), lengths=tuple(counts),
                       slacks=np.concatenate(slacks))


def _make_windowed(m, n, mesh, axes, windows: RankWindows):
    """Each device's grain as rank windows: device d runs its W_d windows
    (rows ``[d·W_d, (d + 1)·W_d)`` of the counts and slacks, sharded over
    ``axes``) in one program of the windowed kernel and adds their
    partials, and one ``psum`` adds the devices'.  The clipped Pascal
    table and the grid extent of the longest window are bound here."""
    from repro.kernels import ops  # lazy: kernels depend on core
    table = jnp.asarray(ops.capped_table(n, m))
    padded, _ = ops.window_grid(windows.lengths)
    counts = jnp.asarray(np.asarray(windows.lengths, np.int32))
    slacks = jnp.asarray(windows.slacks)

    # check_vma=False: pallas_call outputs don't carry vma metadata yet
    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, check_vma=False,
        in_specs=(P(), P(), P(axes), P(axes)), out_specs=P())
    def worker(A_rep, tab, cnt, slk):
        parts = ops.radic_windowed_partial_pallas(A_rep, tab, cnt, slk,
                                                  padded)
        return psum_scalar(parts.sum().astype(A_rep.dtype), axes)

    def evaluate(A: jax.Array) -> jax.Array:
        return worker(jnp.asarray(A), table, counts, slacks)

    return evaluate


def make_batched_distributed_evaluator(
    m: int,
    n: int,
    *,
    mesh: Mesh,
    axis_names: Sequence[str] | None = None,
    batch_axis: str | None = None,
    chunk: int = 1024,
    backend: Literal["jnp", "pallas"] = "jnp",
):
    """Plan-time half of the batched mesh evaluation for one (m, n).

    Returns ``evaluate(As: (B, m, n)) -> (B,)``.  When ``batch_axis`` is
    given the batch dim is sharded over that mesh axis (``B`` must divide
    its size — checked per call, the only per-call validation left) and
    the rank space over the remaining axes; otherwise the batch is
    replicated and the rank space is cut over every axis.
    """
    axes = tuple(axis_names if axis_names is not None else mesh.axis_names)
    if batch_axis is not None:
        if batch_axis not in axes:
            raise ValueError(f"batch_axis {batch_axis!r} not in {axes}")
        rank_axes = tuple(a for a in axes if a != batch_axis)
    else:
        rank_axes = axes
    total = validate_rank_space(m, n, backend=backend)
    table = rank_table(n, m)  # int64 under x64, int32 otherwise
    D = math.prod(mesh.shape[a] for a in rank_axes)
    starts_q, lengths = plan_grains(total, D)
    tdtype = table.dtype
    starts_q = jnp.asarray(np.array(starts_q, dtype=tdtype))
    lengths_a = jnp.asarray(np.array(lengths, dtype=tdtype))
    max_len = max(lengths)
    chunk = int(min(chunk, max(max_len, 1)))
    num_chunks = -(-max_len // chunk)

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, check_vma=False,
        in_specs=(P(batch_axis), P(), P(rank_axes), P(rank_axes)),
        out_specs=P(batch_axis))
    def worker(As_loc, tab, q0, cnt):
        q0 = q0[0]
        cnt = cnt[0]
        if backend == "pallas":
            from repro.kernels import ops
            acc = ops.radic_batched_partial_pallas(As_loc, tab, q0, cnt,
                                                   num_chunks * chunk)
        else:
            idx = jnp.arange(chunk, dtype=tab.dtype)

            def body(c, acc):
                qs = q0 + c.astype(tab.dtype) * chunk + idx
                valid = qs < q0 + cnt
                combos = unrank_jnp(jnp.where(valid, qs, 0), n, m, tab)
                return acc + signed_minor_sum_batched(As_loc, combos, valid)

            zero = pvary(jnp.zeros((As_loc.shape[0],), As_loc.dtype),
                         rank_axes)
            acc = jax.lax.fori_loop(0, num_chunks, body, zero)
        return psum_scalar(acc, rank_axes)

    def evaluate(As: jax.Array) -> jax.Array:
        As = jnp.asarray(As)
        if As.ndim != 3 or As.shape[1:] != (m, n):
            raise ValueError(f"expected (B, {m}, {n}), got {As.shape}")
        if batch_axis is not None and As.shape[0] % mesh.shape[batch_axis]:
            raise ValueError(
                f"batch {As.shape[0]} is not divisible by mesh axis "
                f"{batch_axis} size {mesh.shape[batch_axis]}")
        return worker(As, table, starts_q, lengths_a)

    return evaluate


def make_batched_distributed_grad_evaluator(
    m: int,
    n: int,
    *,
    mesh: Mesh,
    axis_names: Sequence[str] | None = None,
    batch_axis: str | None = None,
    chunk: int = 1024,
    backend: Literal["jnp", "pallas"] = "jnp",
):
    """Cofactor-form VJP of :func:`make_batched_distributed_evaluator`.

    Returns ``grad(As: (B, m, n), cts: (B,)) -> (B, m, n)``.  Sharding
    mirrors the forward exactly — batch over ``batch_axis``, rank space
    over the remaining axes — and each rank shard pulls the cotangents
    back through its own chunk walk, so the tree-sum of forward partials
    becomes a ``psum`` of per-shard gradient partials over the same rank
    axes (DESIGN_GRAD.md).  All collectives go through
    :mod:`repro.parallel.compat`.
    """
    axes = tuple(axis_names if axis_names is not None else mesh.axis_names)
    if batch_axis is not None:
        if batch_axis not in axes:
            raise ValueError(f"batch_axis {batch_axis!r} not in {axes}")
        rank_axes = tuple(a for a in axes if a != batch_axis)
    else:
        rank_axes = axes
    total = validate_rank_space(m, n, backend=backend)
    table = rank_table(n, m)  # int64 under x64, int32 otherwise
    D = math.prod(mesh.shape[a] for a in rank_axes)
    starts_q, lengths = plan_grains(total, D)
    tdtype = table.dtype
    starts_q = jnp.asarray(np.array(starts_q, dtype=tdtype))
    lengths_a = jnp.asarray(np.array(lengths, dtype=tdtype))
    max_len = max(lengths)
    chunk = int(min(chunk, max(max_len, 1)))
    num_chunks = -(-max_len // chunk)

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, check_vma=False,
        in_specs=(P(batch_axis), P(batch_axis), P(), P(rank_axes),
                  P(rank_axes)),
        out_specs=P(batch_axis))
    def grad_worker(As_loc, cts_loc, tab, q0, cnt):
        q0 = q0[0]
        cnt = cnt[0]
        if backend == "pallas":
            from repro.kernels import radic_fused
            g = radic_fused.radic_batched_grad_partial_pallas(
                As_loc, cts_loc, tab, q0, cnt, num_chunks * chunk)
        else:
            idx = jnp.arange(chunk, dtype=tab.dtype)

            def body(c, g):
                qs = q0 + c.astype(tab.dtype) * chunk + idx
                valid = qs < q0 + cnt
                combos = unrank_jnp(jnp.where(valid, qs, 0), n, m, tab)
                _, pull = jax.vjp(
                    lambda a: signed_minor_sum_batched(a, combos, valid),
                    As_loc)
                (gAs,) = pull(cts_loc)
                return g + gAs

            zero = pvary(jnp.zeros_like(As_loc), rank_axes)
            g = jax.lax.fori_loop(0, num_chunks, body, zero)
        return psum_scalar(g, rank_axes)

    def grad(As: jax.Array, cts) -> jax.Array:
        As = jnp.asarray(As)
        if As.ndim != 3 or As.shape[1:] != (m, n):
            raise ValueError(f"expected (B, {m}, {n}), got {As.shape}")
        if batch_axis is not None and As.shape[0] % mesh.shape[batch_axis]:
            raise ValueError(
                f"batch {As.shape[0]} is not divisible by mesh axis "
                f"{batch_axis} size {mesh.shape[batch_axis]}")
        cts = jnp.reshape(jnp.asarray(cts, As.dtype), (As.shape[0],))
        return grad_worker(As, cts, table, starts_q, lengths_a)

    return grad


# ------------------------------------------------------- engine-routed entry
def radic_det_distributed(
    A: jax.Array,
    *,
    mesh: Mesh | None = None,
    axis_names: Sequence[str] | None = None,
    grains_per_device: int = 1,
    mode: Literal["grains", "flat"] = "grains",
    chunk: int = 1024,
    backend: Literal["jnp", "pallas"] = "jnp",
) -> jax.Array:
    """Radic determinant distributed over a device mesh.

    ``A`` is replicated (it is tiny — m×n); the rank space is sharded.
    Returns a replicated scalar.  Routed through the default
    :class:`~repro.core.engine.DetEngine`, so the host-side grain
    planning is cached per (shape, mesh, mode) and paid once.
    """
    from .engine import default_engine  # lazy: engine routes back here
    A = jnp.asarray(A)
    m, n = A.shape
    mesh = mesh if mesh is not None else _default_mesh()
    return default_engine().plan(
        m, n, batched=False, dtype=A.dtype, chunk=chunk, backend=backend,
        mesh=mesh, axis_names=axis_names, mode=mode,
        grains_per_device=grains_per_device).differentiable(A)


def radic_det_batched_distributed(
    As: jax.Array,
    *,
    mesh: Mesh | None = None,
    axis_names: Sequence[str] | None = None,
    batch_axis: str | None = None,
    chunk: int = 1024,
    backend: Literal["jnp", "pallas"] = "jnp",
) -> jax.Array:
    """Batched Radic determinants sharded rank-space × batch over a mesh.

    ``As (B, m, n)`` — one shared (m, n) shape, so the whole batch walks a
    single rank space with one Pascal table.  Returns ``(B,)``.  Routed
    through the default engine (one planned worker per shape × mesh).
    """
    from .engine import default_engine  # lazy: engine routes back here
    As = jnp.asarray(As)
    B, m, n = As.shape
    mesh = mesh if mesh is not None else _default_mesh()
    return default_engine().plan(
        m, n, batched=True, dtype=As.dtype, chunk=chunk, backend=backend,
        mesh=mesh, axis_names=axis_names, batch_axis=batch_axis
        ).differentiable(As)
