"""Public wrappers around the Pallas kernels: plan-time rank guards,
Pascal table, tile padding and dtype policy.  The kernels compile for
the TPU and run in interpret mode on other backends (CPU tests)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import (plan_rank_windows, validate_rank_space,
                               window_count)
from repro.core.pascal import INT32_MAX, binom_table

from .minor_det import minor_det_pallas
from .radic_fused import (radic_batched_grad_partial_pallas,
                          radic_batched_partial_pallas,
                          radic_batched_partial_pallas_bygrid,
                          radic_partial_pallas)
from .radic_windowed import radic_windowed_partial_pallas
from .unrank_kernel import unrank_pallas

__all__ = ["minor_det", "unrank", "radic_partial_pallas",
           "radic_det_pallas", "capped_table",
           "radic_windowed_partial_pallas", "radic_batched_partial_pallas",
           "radic_det_batched_pallas", "radic_det_batched_pallas_bygrid",
           "radic_det_grad_pallas", "radic_det_batched_grad_pallas"]


def minor_det(mats: jax.Array, *, tile: int = 128,
              interpret: bool | None = None) -> jax.Array:
    """Batched determinant of ``(B, m, m)`` minors."""
    return minor_det_pallas(mats, tile=tile, interpret=interpret)


def unrank(qs: jax.Array, n: int, m: int, *, tile: int = 256,
           interpret: bool | None = None) -> jax.Array:
    """Batched rank → 1-indexed combination."""
    # same plan-time guard as the det wrappers: the kernel's int32 rank
    # arithmetic is a hard limit, and an unguarded table would wrap
    validate_rank_space(m, n, backend="pallas")
    table = jnp.asarray(binom_table(n, m, dtype=np.int32))
    return unrank_pallas(qs, n, m, table, tile=tile, interpret=interpret)


def capped_table(n: int, m: int) -> np.ndarray:
    """The Pascal table of the windowed kernel: int32, entries clipped at
    ``INT32_MAX`` (``common.unrank_window_lanes`` says why that is
    exact)."""
    # the windowed kernel's own guard: only int32 offsets meet this table
    validate_rank_space(m, n, backend="pallas", windowed=True)
    return np.minimum(binom_table(n, m, dtype=np.int64),
                      INT32_MAX).astype(np.int32)


def radic_det_pallas(A: jax.Array, q_start: int = 0, count: int | None = None,
                     *, tile: int = 256, interpret: bool | None = None,
                     windows=None) -> jax.Array:
    """Radic determinant (or a rank-range partial) via the fused kernel.

    Past ``WINDOW_RANKS`` ranks (C(n, m) past int32) the range runs as
    rank windows in the windowed kernel, and ``q_start`` may be any host
    int: ``windows`` takes the range's ``plan_rank_windows``, which a
    plan computes once; without it they are planned here."""
    m, n = A.shape
    if m > n:
        return jnp.zeros((), A.dtype)
    # shared plan validation: past int32 only as rank windows
    total = validate_rank_space(m, n, backend="pallas", windowed=True)
    if count is None:
        count = total - q_start
    if q_start + count > total:
        raise ValueError("rank range exceeds C(n, m)")
    if window_count(total) is not None:
        if windows is None:
            windows = plan_rank_windows(m, n, q_start, count)
        longest = max(windows.lengths)
        padded = max(tile, ((longest + tile - 1) // tile) * tile)
        parts = radic_windowed_partial_pallas(
            A, jnp.asarray(capped_table(n, m)),
            np.asarray(windows.lengths, np.int32), windows.slacks, padded,
            tile=tile, interpret=interpret)
        return parts.sum().astype(A.dtype)
    table = jnp.asarray(binom_table(n, m, dtype=np.int32))
    padded = max(tile, ((count + tile - 1) // tile) * tile)
    return radic_partial_pallas(A, table, q_start, count, padded,
                                tile=tile, interpret=interpret)


def radic_det_batched_pallas(As: jax.Array, q_start: int = 0,
                             count: int | None = None, *, tile: int = 256,
                             interpret: bool | None = None) -> jax.Array:
    """Batched Radic determinants (or rank-range partials) for a
    shape-uniform stack ``As (B, m, n)`` via the combo-reuse fused kernel
    -> ``(B,)``.  The rank tile is unranked once per grid step and shared
    across the batch."""
    B, m, n = As.shape
    if m > n:
        return jnp.zeros((B,), As.dtype)
    # shared plan validation: int32 rank width is a hard kernel limit
    total = validate_rank_space(m, n, backend="pallas")
    if count is None:
        count = total - q_start
    if q_start + count > total:
        raise ValueError("rank range exceeds C(n, m)")
    table = jnp.asarray(binom_table(n, m, dtype=np.int32))
    padded = max(tile, ((count + tile - 1) // tile) * tile)
    return radic_batched_partial_pallas(As, table, q_start, count, padded,
                                        tile=tile, interpret=interpret)


def radic_det_batched_grad_pallas(As: jax.Array, cts: jax.Array,
                                  q_start: int = 0, count: int | None = None,
                                  *, tile: int = 256,
                                  interpret: bool | None = None) -> jax.Array:
    """Cofactor-form VJP of :func:`radic_det_batched_pallas`: pull the
    per-matrix cotangents ``cts (B,)`` back through the same rank walk
    -> ``(B, m, n)`` (see DESIGN_GRAD.md)."""
    As = jnp.asarray(As)
    B, m, n = As.shape
    if m > n:
        return jnp.zeros_like(As)
    # shared plan validation: int32 rank width is a hard kernel limit
    total = validate_rank_space(m, n, backend="pallas")
    if count is None:
        count = total - q_start
    if q_start + count > total:
        raise ValueError("rank range exceeds C(n, m)")
    table = jnp.asarray(binom_table(n, m, dtype=np.int32))
    padded = max(tile, ((count + tile - 1) // tile) * tile)
    cts = jnp.reshape(jnp.asarray(cts, As.dtype), (B,))
    return radic_batched_grad_partial_pallas(
        As, cts, table, q_start, count, padded, tile=tile,
        interpret=interpret)


def radic_det_grad_pallas(A: jax.Array, ct, q_start: int = 0,
                          count: int | None = None, *, tile: int = 256,
                          interpret: bool | None = None) -> jax.Array:
    """Scalar-matrix VJP: ``A (m, n)``, scalar ``ct`` -> ``(m, n)``.
    Dispatches the batched grad kernel at B=1 — same guards, same walk."""
    A = jnp.asarray(A)
    m, n = A.shape
    if m > n:
        return jnp.zeros_like(A)
    cts = jnp.reshape(jnp.asarray(ct, A.dtype), (1,))
    return radic_det_batched_grad_pallas(
        A[None], cts, q_start, count, tile=tile, interpret=interpret)[0]


def radic_det_batched_pallas_bygrid(As: jax.Array, q_start: int = 0,
                                    count: int | None = None, *,
                                    tile: int = 256,
                                    interpret: bool | None = None
                                    ) -> jax.Array:
    """Legacy ``(B, num_tiles)``-grid batched dispatch, kept behind the
    same guards as the default path so the parity tests and benchmarks
    can price the combo-reuse kernel against it."""
    B, m, n = As.shape
    if m > n:
        return jnp.zeros((B,), As.dtype)
    # shared plan validation: int32 rank width is a hard kernel limit
    total = validate_rank_space(m, n, backend="pallas")
    if count is None:
        count = total - q_start
    if q_start + count > total:
        raise ValueError("rank range exceeds C(n, m)")
    table = jnp.asarray(binom_table(n, m, dtype=np.int32))
    padded = max(tile, ((count + tile - 1) // tile) * tile)
    return radic_batched_partial_pallas_bygrid(
        As, table, q_start, count, padded, tile=tile, interpret=interpret)
