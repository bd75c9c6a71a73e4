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

from .radic_fused import (SUBLANES, radic_batched_grad_partial_pallas,
                          radic_batched_partial_pallas,
                          radic_partial_pallas)
from .radic_windowed import grid_steps, radic_windowed_partial_pallas

__all__ = ["radic_partial_pallas", "radic_det_pallas", "capped_table",
           "radic_windowed_partial_pallas", "radic_batched_partial_pallas",
           "radic_det_batched_pallas", "radic_det_grad_pallas",
           "radic_det_batched_grad_pallas", "window_grid"]


def _grid_extent(ranks: int, tile: int) -> int:
    """The static grid extent of a kernel over ``ranks`` ranks: at least
    one tile, tile-aligned."""
    return max(tile, ((ranks + tile - 1) // tile) * tile)


def _rank_range(m: int, n: int, q_start: int, count: int | None,
                tile: int, *, windowed: bool = False):
    """The plan-time guard every entry point shares: C(n, m) held to the
    kernels' int32 ranks (past them only ``windowed``, as rank windows),
    ``count`` defaulting to the rest of the rank space, and the range
    held inside it.  Returns ``(count, table, padded)``, the int32 Pascal
    table and the grid extent of one kernel over the range; ``table`` and
    ``padded`` are None where the range runs as rank windows."""
    total = validate_rank_space(m, n, backend="pallas", windowed=windowed)
    if count is None:
        count = total - q_start
    if q_start + count > total:
        raise ValueError("rank range exceeds C(n, m)")
    if windowed and window_count(total) is not None:
        return count, None, None
    table = jnp.asarray(binom_table(n, m, dtype=np.int32))
    return count, table, _grid_extent(count, tile)


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
    count, table, padded = _rank_range(m, n, q_start, count, tile,
                                       windowed=True)
    if table is None:
        if windows is None:
            windows = plan_rank_windows(m, n, q_start, count)
        parts = radic_windowed_partial_pallas(
            A, jnp.asarray(capped_table(n, m)),
            np.asarray(windows.lengths, np.int32), windows.slacks,
            _grid_extent(max(windows.lengths), tile), tile=tile,
            interpret=interpret)
        return parts.sum().astype(A.dtype)
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
    count, table, padded = _rank_range(m, n, q_start, count, tile)
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
    count, table, padded = _rank_range(m, n, q_start, count, tile)
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


def window_grid(lengths, tile: int = 256) -> tuple[int, int]:
    """The windowed kernel's grid over windows of ``lengths`` ranks: the
    static grid extent of the longest, and the rank slots the whole grid
    covers, W · steps · 8 · ``tile``, which ``DetEngine`` counts as
    ``grid_ranks``."""
    padded = _grid_extent(max(lengths), tile)
    return padded, len(lengths) * grid_steps(padded, tile) * SUBLANES * tile
