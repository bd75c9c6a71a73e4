"""The fused value kernel over rank windows: one matrix past int32.

A Radic determinant of one m×n matrix whose rank space C(n, m) is past
``core.engine.WINDOW_RANKS`` (about 2³¹) cannot give each lane its
global rank in int32.  The engine cuts the rank space into the fewest
contiguous windows of at most ``WINDOW_RANKS`` ranks (``plan_grains``'
even cut) and unranks each window's start on the host with exact ints
(``core.engine.plan_rank_windows``).  This kernel runs them all:

    window w, step i ──offsets d < 2³¹ (int32)──► unrank relative to the
                     window's start (common.unrank_window_lanes)
                     ──► the B = 1 pipeline of radic_fused: 8 rank rows
                     on the sublanes, gather from A's bf16 parts,
                     pivoted GE, sign · mask

The grid is ``(W, steps)``: each window's whole steps and tail (SMEM,
``qinfo``), its ``(n,)`` slacks (SMEM) and the Pascal table clipped at
``INT32_MAX`` are scalar-prefetch operands; window w adds its per-lane
terms into output block w, and the wrapper returns one partial per
window.  Every window runs in one program with no host round trip.
Plans within int32 never build this kernel: their programs are
``radic_fused``'s, unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (det_slabs, rank_row_operand, rank_row_slabs,
                     sign_lanes, unrank_window_lanes)
from .radic_fused import SUBLANES, lane_ranks, resolve_interpret

__all__ = ["radic_windowed_combo_kernel", "radic_windowed_partial_pallas"]


def radic_windowed_combo_kernel(n: int, m: int, tile: int, qinfo_ref,
                                slack_ref, table_ref, a_ref, out_ref):
    """Step ``i`` of window ``w``: 8 rank rows of the one matrix at window
    offsets ``i·8T + s·T + lane``, as the B = 1 value kernel lays them
    out; the window's per-lane terms go to block ``w``."""
    w = pl.program_id(0)
    pid = pl.program_id(1)
    valid, ds = lane_ranks(pid, 0, qinfo_ref[2 * w], qinfo_ref[2 * w + 1],
                           SUBLANES, tile)
    # int32 window offsets; the windows are planned at the ops.py entry
    combos = unrank_window_lanes(  # reprolint: disable=overflow-guard
        ds, n, m, table_ref, slack_ref, w * n - 1)
    signs = sign_lanes(combos, m)

    @pl.when(pid == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dets = det_slabs(rank_row_slabs(a_ref[0], combos))
    out_ref[0] += jnp.where(valid, signs * dets, 0.0)


@functools.partial(jax.jit,
                   static_argnames=("padded_count", "tile", "interpret"))
def radic_windowed_partial_pallas(A: jax.Array, table: jax.Array,
                                  counts: jax.Array, slacks: jax.Array,
                                  padded_count: int, *, tile: int = 256,
                                  interpret: bool | None = None
                                  ) -> jax.Array:
    """Per-window Σ sign·det of one ``A (m, n)`` -> ``(W,)``: window w
    covers ``counts[w]`` ranks from its start, whose slacks are
    ``slacks[w] (n,)``; ``table`` is the Pascal table clipped at
    ``INT32_MAX``, ``padded_count`` the static grid extent of the longest
    window."""
    m, n = A.shape
    parts = rank_row_operand(A)
    W = counts.shape[0]
    step = SUBLANES * tile
    counts = jnp.asarray(counts, jnp.int32)
    qinfo = jnp.stack([counts // step, counts % step], axis=1).reshape(-1)

    out = pl.pallas_call(
        functools.partial(radic_windowed_combo_kernel, n, m, tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(W, max(1, -(-padded_count // step))),
            in_specs=[pl.BlockSpec((1, *parts.shape),
                                   lambda w, i, *_: (0, 0, 0))],
            out_specs=pl.BlockSpec((1, SUBLANES, tile),
                                   lambda w, i, *_: (w, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((W, SUBLANES, tile), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="radic_windowed_partial_pallas",
    )(qinfo, jnp.asarray(slacks, jnp.int32).reshape(-1),
      table.astype(jnp.int32).reshape(-1), parts[None])
    # the sublanes and lanes of a window first, as the B = 1 kernel sums
    return out.sum(axis=(1, 2))
