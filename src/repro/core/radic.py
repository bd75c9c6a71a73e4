"""Radic determinant of an m×n matrix (paper Definition 3) — JAX path.

``det(A) = Σ_q (−1)^(r + s_q) · det(A[:, B_q])`` over all ``C(n, m)``
column subsets ``B_q`` in dictionary order, where ``r = m(m+1)/2`` and
``s_q`` is the (1-indexed) column sum of ``B_q``.

The flat mode streams the rank space in fixed-size chunks: each chunk is
unranked independently (the paper's contribution — no dependency between
minors), gathered, evaluated and accumulated.  Signs, masking and the
optional Kahan compensation live here; the per-chunk math is shared with
the Pallas kernel's oracle.
"""

from __future__ import annotations

import functools
import math
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from .unrank import unrank_jnp

__all__ = ["radic_det", "radic_det_batched", "make_batched_evaluator",
           "aot_compile_batched", "signed_minor_sum",
           "signed_minor_sum_batched", "radic_sign"]


def radic_sign(combos: jax.Array, m: int) -> jax.Array:
    """(−1)^(r+s) for a batch of 1-indexed combinations ``(B, m)``."""
    r = m * (m + 1) // 2
    parity = (jnp.sum(combos, axis=1) + r) & 1
    return (1 - 2 * parity).astype(jnp.float32)


def signed_minor_sum(A: jax.Array, combos: jax.Array,
                     valid: jax.Array | None = None) -> jax.Array:
    """Σ sign(B_q)·det(A[:, B_q]) for a batch of combinations.

    ``A (m, n)``, ``combos (B, m)`` 1-indexed.  Uses the transposed-minor
    trick: ``det(A[:, J]) == det(A.T[J, :])`` so the gather is a single
    row-take.  Pure jnp — this is also the oracle body for the fused
    Pallas kernel.
    """
    m = A.shape[0]
    minors = jnp.take(A.T, combos - 1, axis=0)  # (B, m, m) transposed minors
    dets = jnp.linalg.det(minors)
    signs = radic_sign(combos, m).astype(dets.dtype)
    terms = signs * dets
    if valid is not None:
        terms = jnp.where(valid, terms, 0)
    return jnp.sum(terms)


def signed_minor_sum_batched(As: jax.Array, combos: jax.Array,
                             valid: jax.Array | None = None) -> jax.Array:
    """Batched-matrix form of :func:`signed_minor_sum`.

    ``As (B, m, n)``, ``combos (C, m)`` 1-indexed — the *same* rank chunk
    is applied to every matrix in the batch (one shared unranking, one
    shared sign vector), which is what makes the batched dispatch cheaper
    than B independent calls.  Returns per-matrix partials ``(B,)``.
    """
    m = As.shape[1]
    # (B, n, m) transposed, then one shared row-take -> (B, C, m, m)
    minors = jnp.take(As.transpose(0, 2, 1), combos - 1, axis=1)
    dets = jnp.linalg.det(minors)                       # (B, C)
    signs = radic_sign(combos, m).astype(dets.dtype)    # (C,)
    terms = signs[None, :] * dets
    if valid is not None:
        terms = jnp.where(valid[None, :], terms, 0)
    return jnp.sum(terms, axis=1)


@functools.partial(jax.jit,
                   static_argnames=("total", "chunk", "kahan"))
def _radic_det_flat(A: jax.Array, table: jax.Array, total: int, chunk: int,
                    kahan: bool) -> jax.Array:
    m, n = A.shape
    num_chunks = -(-total // chunk)
    idx = jnp.arange(chunk, dtype=table.dtype)

    def body(c, carry):
        acc, comp = carry
        qs = c.astype(table.dtype) * chunk + idx
        valid = qs < total
        combos = unrank_jnp(jnp.where(valid, qs, 0), n, m, table)
        part = signed_minor_sum(A, combos, valid)
        if kahan:
            y = part - comp
            t = acc + y
            comp = (t - acc) - y
            acc = t
        else:
            acc = acc + part
        return acc, comp

    zero = jnp.zeros((), A.dtype)
    acc, _ = jax.lax.fori_loop(0, num_chunks, body, (zero, zero))
    return acc


def radic_det(A: jax.Array, *, chunk: int = 2048, kahan: bool = False,
              backend: Literal["jnp", "pallas"] = "jnp") -> jax.Array:
    """Radic determinant (paper Definition 3), rank-parallel flat mode.

    Single-device streaming evaluation; for mesh distribution see
    :func:`repro.core.distributed.radic_det_distributed`.  The jnp
    backend requires ``C(n, m) < 2**31`` (int32 ranks) unless x64 is
    enabled — beyond that use the distributed grain mode (bigint grain
    starts).  The pallas backend runs past int32 as rank windows
    (``DetPlan.windows``, the engine's ``plan_rank_windows``): the value
    only, ``jax.grad`` of such a plan raises.  Routed through
    the default :class:`~repro.core.engine.DetEngine`: the rank-width
    guards run at plan time, *before* backend dispatch, and the plan
    (Pascal table, clamped chunk, validated total) is cached per shape.

    Differentiable: the plan routes through a ``jax.custom_vjp`` whose
    backward pass replays the same rank-tile walk in cofactor form
    (DESIGN_GRAD.md), so ``jax.grad(radic_det)`` runs in O(chunk)
    backward memory instead of saving every minor as a residual.
    """
    from .engine import default_engine  # lazy: engine builds on this module
    A = jnp.asarray(A)
    m, n = A.shape
    return default_engine().plan(
        m, n, batched=False, dtype=A.dtype, chunk=chunk, kahan=kahan,
        backend=backend).differentiable(A)


def _radic_det_batched_flat_impl(As: jax.Array, table: jax.Array, total: int,
                                 chunk: int) -> jax.Array:
    B, m, n = As.shape
    num_chunks = -(-total // chunk)
    idx = jnp.arange(chunk, dtype=table.dtype)

    def body(c, acc):
        qs = c.astype(table.dtype) * chunk + idx
        valid = qs < total
        combos = unrank_jnp(jnp.where(valid, qs, 0), n, m, table)
        return acc + signed_minor_sum_batched(As, combos, valid)

    return jax.lax.fori_loop(0, num_chunks, body,
                             jnp.zeros((B,), As.dtype))


_radic_det_batched_flat = functools.partial(
    jax.jit, static_argnames=("total", "chunk"))(_radic_det_batched_flat_impl)


# ------------------------------------------------------------- VJP programs
# Cofactor-form backward pass (DESIGN_GRAD.md): for Radic's definition
# ∂det/∂A[i, j] = Σ_{q : j ∈ B_q} sign(B_q) · ∂det(A[:, B_q])/∂A[i, j],
# a signed sum of (m−1)-order minors over the *same* C(n, m) rank walk
# the forward pays.  Each chunk re-unranks its combinations exactly as
# the forward did and pulls the cotangent back through that chunk's
# minor-sum — no residuals are saved across chunks, so backward memory
# is O(chunk) like the forward, not O(total) like autodiff-of-scan.
@functools.partial(jax.jit, static_argnames=("total", "chunk"))
def _radic_det_grad_flat(A: jax.Array, ct: jax.Array, table: jax.Array,
                         total: int, chunk: int) -> jax.Array:
    m, n = A.shape
    num_chunks = -(-total // chunk)
    idx = jnp.arange(chunk, dtype=table.dtype)

    def body(c, g):
        qs = c.astype(table.dtype) * chunk + idx
        valid = qs < total
        combos = unrank_jnp(jnp.where(valid, qs, 0), n, m, table)
        _, pull = jax.vjp(lambda a: signed_minor_sum(a, combos, valid), A)
        (gA,) = pull(ct)
        return g + gA

    return jax.lax.fori_loop(0, num_chunks, body, jnp.zeros_like(A))


@functools.partial(jax.jit, static_argnames=("total", "chunk"))
def _radic_det_batched_grad_flat(As: jax.Array, cts: jax.Array,
                                 table: jax.Array, total: int,
                                 chunk: int) -> jax.Array:
    """Batched cofactor VJP: ``As (B, m, n)``, ``cts (B,)`` → ``(B, m, n)``.
    One shared unranking per chunk pulls back all B cotangents, the same
    amortization the batched forward gets."""
    B, m, n = As.shape
    num_chunks = -(-total // chunk)
    idx = jnp.arange(chunk, dtype=table.dtype)

    def body(c, g):
        qs = c.astype(table.dtype) * chunk + idx
        valid = qs < total
        combos = unrank_jnp(jnp.where(valid, qs, 0), n, m, table)
        _, pull = jax.vjp(
            lambda a: signed_minor_sum_batched(a, combos, valid), As)
        (gAs,) = pull(cts)
        return g + gAs

    return jax.lax.fori_loop(0, num_chunks, body, jnp.zeros_like(As))

# Same program, but the staged (B, m, n) batch buffer is donated: the
# serving tier stages each batch into a fresh device array that is dead
# the moment the dispatch returns, so on backends with real donation
# (TPU/GPU) XLA may alias it for scratch instead of allocating.  Math is
# untouched — donation is a buffer-aliasing hint, results bit-identical.
# The engine picks this lowering only when the backend supports donation
# (CPU ignores it with a compile-time warning).
_radic_det_batched_flat_donated = functools.partial(
    jax.jit, static_argnames=("total", "chunk"),
    donate_argnums=(0,))(_radic_det_batched_flat_impl)


def make_batched_evaluator(m: int, n: int, *, chunk: int = 2048,
                           backend: Literal["jnp", "pallas"] = "jnp",
                           mesh=None, axis_names=None,
                           batch_axis: str | None = None):
    """Bind the per-shape state of :func:`radic_det_batched` once.

    Returns the :class:`~repro.core.engine.DetPlan` for this shape — a
    callable ``evaluate(As: (B, m, n)) -> (B,)``.  The Pascal table, the
    C(n, m) rank count and the clamped chunk are computed at plan time,
    so a server dispatching many batches of the same shape
    (:mod:`repro.launch.det_queue`) pays the host-side combinatorics once
    per bucket instead of once per dispatch.  The plan enters the same
    jitted program as :func:`radic_det_batched`, so results are
    bit-identical to the one-shot path.  ``m > n`` is normalized to a
    jitted zeros *device* program for every backend/mesh configuration —
    not a host closure.

    The x64 flag is part of the plan key; flipping ``jax_enable_x64``
    after creation re-plans automatically on the next bind.
    """
    from .engine import default_engine  # lazy: engine builds on this module
    return default_engine().plan(
        m, n, batched=True, chunk=chunk, backend=backend, mesh=mesh,
        axis_names=axis_names, batch_axis=batch_axis)


def aot_compile_batched(m: int, n: int, capacity: int, dtype=np.float32, *,
                        chunk: int = 2048):
    """AOT-compile the jnp batched program for one ``(capacity, m, n)``.

    Lowers the *same* jitted function with the same table and statics as
    :func:`radic_det_batched`'s jnp path — the identical XLA program, so
    results are bit-identical to the traced-call path — but the
    per-dispatch python (jit-cache lookup, argument processing) is paid
    once here instead of on every call.  This is the dispatcher hot path
    of :class:`repro.launch.det_queue.DetQueue`.  Returns the
    :class:`~repro.core.engine.DetPlan`, callable as
    ``exe(As: (capacity, m, n) device array) -> (capacity,)``.  ``m > n``
    degenerates to the jitted zeros program (nothing to lower).
    """
    from .engine import default_engine  # lazy: engine builds on this module
    return default_engine().plan(
        m, n, batched=True, capacity=capacity, dtype=dtype, chunk=chunk)


def radic_det_batched(As: jax.Array, *, chunk: int = 2048,
                      backend: Literal["jnp", "pallas"] = "jnp",
                      mesh=None, axis_names=None,
                      batch_axis: str | None = None) -> jax.Array:
    """Radic determinants of a stack ``As (B, m, n)`` in one dispatch.

    The whole batch shares one (m, n) shape, hence one C(n, m) rank
    space, one Pascal table and one unranking per chunk — the per-rank
    combinatorics are amortized over B matrices (the GPU-batching
    strategy of Wei & Chen 2020 applied to Radic's definition).
    Heterogeneously-shaped inputs should be bucketed by shape first; see
    :mod:`repro.launch.det_serve`.  Returns ``(B,)``.

    With ``mesh`` the evaluation is sharded rank-space × batch over the
    mesh (see :func:`repro.core.distributed.radic_det_batched_distributed`).
    Repeated same-shape dispatches should bind the shape once via
    :func:`make_batched_evaluator`.
    """
    As = jnp.asarray(As)
    if As.ndim != 3:
        raise ValueError(f"expected (B, m, n), got {As.shape}")
    B, m, n = As.shape
    if B == 0:
        return jnp.zeros((0,), As.dtype)
    return make_batched_evaluator(
        m, n, chunk=chunk, backend=backend, mesh=mesh,
        axis_names=axis_names, batch_axis=batch_axis).differentiable(As)
