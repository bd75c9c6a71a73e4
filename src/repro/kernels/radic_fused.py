"""Fused Radic-partial Pallas kernels: forward and cofactor-form backward.

One kernel = the paper's whole per-processor pipeline, fused so minors
never touch HBM:

    rank tile ──unrank (VPU, n static steps)────► combos, m (R, T) rows
              ──one-hot matmuls (MXU)───────────► minor slabs (8, T)
              ──pivoted GE (VPU, unrolled in m)─► dets (8, T)
              ──sign · mask─────────────────────► per-lane partials

Two layouts of the 8 f32 sublanes, chosen from the static batch size
(:func:`rank_rows`):

* B ≥ 2, matrices on sublanes (R = 1): the T ranks of a tile sit on the
  128-wide lane axis and the batch on the sublanes, 8 matrices per
  group.  A grid step unranks its tile once, builds the selectors and
  signs once, then walks the batch group by group, so that shared work
  is paid once per tile and not once per matrix.  The batch enters the
  kernel group-major, ``(G, 8·m, n)`` with row ``a·8 + s`` of group g
  holding row a of matrix ``8g + s``; the wrappers pad B up to a
  multiple of 8 with zero matrices, whose results are sliced off.
* B = 1, rank rows on sublanes (R = 8): sublane s of a grid step holds
  ranks ``step·8T + s·T + lane`` of the one matrix, so a step covers 8T
  ranks and no sublane computes a zero matrix.  The matrix enters as its
  exact bf16 parts side by side, ``(1, m, 3·np)``, and each rank row
  takes one bf16 one-hot matmul whose f32 outputs are A's entries bit
  for bit (``common.rank_row_slabs``); the elimination reads the slabs.
  B ≥ 2 and the gradient gather f32 at ``Precision.HIGHEST``.

``q_start``, the whole steps and the tail of ``count``, and the
flattened Pascal table are scalar-prefetch operands (SMEM).  The
outputs stay resident across the sequential grid: the forward adds its
masked per-lane terms into a ``(G, 8, T)`` block that the wrapper sums
over lanes (and over sublanes at B = 1), the backward adds
``(G, 8·m, n)`` gradient partials.  HBM input per tile is zero beyond
the resident A and table — ranks are generated from the grid index.
The rank-window kernel (``radic_windowed.py``) runs the B = 1 layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (det_slabs, minor_slabs, rank_row_operand,
                     rank_row_slabs, selector_slabs, sign_lanes,
                     unrank_lanes)

__all__ = ["radic_partial_pallas", "radic_batched_combo_kernel",
           "radic_batched_partial_pallas",
           "radic_batched_grad_combo_kernel",
           "radic_batched_grad_partial_pallas", "rank_rows",
           "resolve_interpret"]

SUBLANES = 8  # an f32 sublane tile: matrices per group, rank rows at B = 1


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> compile for the TPU when the backend is one, run the
    Pallas interpreter otherwise (the CPU test path)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def rank_rows(batch: int) -> int:
    """Rank rows per sublane group of the value kernel for a stack of
    ``batch`` matrices: 8 when one matrix fills the sublanes with its own
    ranks (B = 1), 1 when the matrices share each rank row."""
    return SUBLANES if batch == 1 else 1


def _steps(count, step: int) -> list[jax.Array]:
    """``count`` ranks as whole grid steps of ``step`` ranks and a tail:
    the scalars :func:`lane_ranks` reads, so no rank offset in the kernel
    is ever formed past ``count``."""
    count = jnp.asarray(count, jnp.int32)
    return [count // step, count % step]


def lane_ranks(pid, q_start, full, tail, rows: int, tile: int):
    """Validity mask and ranks ``(rows, tile)`` of grid step ``pid``, for
    ``count = full·step + tail`` ranks from ``q_start``, ``step =
    rows·tile``: lane ``(s, t)`` is rank ``q_start + pid·step + s·tile +
    t`` where that lies inside the range.

    Every offset is bounded by ``count``: the base is taken at
    ``min(pid, full)`` steps and a masked lane adds nothing, so a grid
    that runs past the range — the padded grid extent, or a shard's
    shorter grain — forms no offset that could wrap int32.  A masked lane
    unranks a rank of ``[q_start, q_start + count]`` and is dropped."""
    step = rows * tile
    local = (jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 0) * tile
             + jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 1))
    limit = jnp.where(pid < full, step, jnp.where(pid == full, tail, 0))
    valid = local < limit
    base = jnp.minimum(pid, full) * step
    return valid, q_start + base + jnp.where(valid, local, 0)


def _tile_setup(n: int, m: int, tile: int, rows: int, qinfo_ref,
                table_ref):
    """The rank tile of this grid step: lane validity mask, combinations
    and signs, ``(rows, tile)`` — shared by every matrix of the batch
    when ``rows`` is 1."""
    valid, qs = lane_ranks(pl.program_id(0), qinfo_ref[0], qinfo_ref[1],
                           qinfo_ref[2], rows, tile)
    # in-kernel unranking; guarded at the ops.py entry points
    combos = unrank_lanes(qs, n, m, table_ref)  # reprolint: disable=overflow-guard
    return valid, combos, sign_lanes(combos, m)


def radic_batched_combo_kernel(n: int, m: int, tile: int, rows: int,
                               groups: int, qinfo_ref, table_ref, a_ref,
                               out_ref):
    """Forward: per-lane Σ sign·det of this tile for every matrix, or at
    ``rows`` = 8 for the rank rows of the one matrix, from its bf16 parts."""
    valid, combos, signs = _tile_setup(n, m, tile, rows, qinfo_ref,
                                       table_ref)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    if rows > 1:
        dets = det_slabs(rank_row_slabs(a_ref[0], combos))
        out_ref[0] += jnp.where(valid, signs * dets, 0.0)
        return
    sel = selector_slabs(combos, n)

    def group(g, carry):
        dets = det_slabs(minor_slabs(a_ref[g], sel))
        out_ref[g] += jnp.where(valid, signs * dets, 0.0)
        return carry

    jax.lax.fori_loop(0, groups, group, 0)


def radic_batched_grad_combo_kernel(n: int, m: int, tile: int, groups: int,
                                    qinfo_ref, table_ref, a_ref, ct_ref,
                                    out_ref):
    """Cofactor-form VJP: replays the forward tile exactly — same
    unranking, selectors, signs and GE lanes — and pulls each group's
    cotangents back through that tile's terms (DESIGN_GRAD.md).  No
    residual crosses the tile boundary, so backward VMEM stays the
    forward's O(8·T·m²) per group."""
    valid, combos, signs = _tile_setup(n, m, tile, 1, qinfo_ref, table_ref)
    sel = selector_slabs(combos, n)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def lane_terms(a):
        return jnp.where(valid, signs * det_slabs(minor_slabs(a, sel)), 0.0)

    def group(g, carry):
        _, pull = jax.vjp(lane_terms, a_ref[g])
        (ga,) = pull(jnp.broadcast_to(ct_ref[g], (SUBLANES, tile)))
        out_ref[g] += ga
        return carry

    jax.lax.fori_loop(0, groups, group, 0)


def _grouped(As: jax.Array) -> jax.Array:
    """``(B, m, n)`` -> group-major f32 ``(G, 8·m, n)``, B padded to 8G."""
    B, m, n = As.shape
    G = -(-B // SUBLANES)
    As = jnp.pad(As.astype(jnp.float32),
                 ((0, G * SUBLANES - B), (0, 0), (0, 0)))
    return (As.reshape(G, SUBLANES, m, n).transpose(0, 2, 1, 3)
            .reshape(G, m * SUBLANES, n))


def _ungrouped(a: jax.Array, B: int, m: int, n: int) -> jax.Array:
    G = a.shape[0]
    return (a.reshape(G, m, SUBLANES, n).transpose(0, 2, 1, 3)
            .reshape(G * SUBLANES, m, n)[:B])


def _pallas_tiles(body, padded_count: int, step: int, table, q_start,
                  count, operands, out_shape, interpret, name: str):
    """One sequential grid step per ``step`` ranks; every operand and the
    output are whole-array blocks resident in VMEM.  ``name`` is the HLO
    instruction's name, which a device trace shows and the benchmark's
    kernel readers match (``tests/test_tpu_compile.py`` pins it)."""

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    qinfo = jnp.stack([jnp.asarray(q_start, jnp.int32),
                       *_steps(count, step)])
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(max(1, -(-padded_count // step)),),
            in_specs=[whole(x.shape) for x in operands],
            out_specs=whole(out_shape)),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
        name=name,
    )(qinfo, table.astype(jnp.int32).reshape(-1), *operands)


@functools.partial(jax.jit,
                   static_argnames=("padded_count", "tile", "interpret"))
def radic_batched_partial_pallas(As: jax.Array, table: jax.Array,
                                 q_start: jax.Array | int,
                                 count: jax.Array | int,
                                 padded_count: int, *, tile: int = 256,
                                 interpret: bool | None = None) -> jax.Array:
    """Per-matrix Σ sign·det over ranks [q_start, q_start+count) for a
    shape-uniform stack ``As (B, m, n)`` -> ``(B,)``; ``padded_count`` is
    the static grid extent (≥ count, tile-aligned).  At B = 1 the kernel
    puts 8 rank rows of the one matrix on the sublanes (:func:`rank_rows`)
    and a grid step covers ``8·tile`` ranks."""
    B, m, n = As.shape
    rows = rank_rows(B)
    a = _grouped(As) if rows == 1 else rank_row_operand(As[0])[None]
    G = a.shape[0]
    out = _pallas_tiles(
        functools.partial(radic_batched_combo_kernel, n, m, tile, rows, G),
        padded_count, rows * tile, table, q_start, count, [a],
        (G, SUBLANES, tile), interpret, "radic_batched_partial_pallas")
    if rows > 1:  # the sublanes first: a range under one row keeps its sum
        out = out.sum(axis=1)
    return out.reshape(-1, tile)[:B].sum(axis=1).astype(As.dtype)


def radic_partial_pallas(A: jax.Array, table: jax.Array,
                         q_start: jax.Array | int, count: jax.Array | int,
                         padded_count: int, *, tile: int = 256,
                         interpret: bool | None = None) -> jax.Array:
    """Σ sign·det over ranks [q_start, q_start+count) of one ``A (m, n)``:
    the batched kernel at B = 1, 8 rank rows per grid step."""
    return radic_batched_partial_pallas(
        A[None], table, q_start, count, padded_count, tile=tile,
        interpret=interpret)[0]


@functools.partial(jax.jit,
                   static_argnames=("padded_count", "tile", "interpret"))
def radic_batched_grad_partial_pallas(As: jax.Array, cts: jax.Array,
                                      table: jax.Array,
                                      q_start: jax.Array | int,
                                      count: jax.Array | int,
                                      padded_count: int, *, tile: int = 256,
                                      interpret: bool | None = None
                                      ) -> jax.Array:
    """Gradient partial over ranks [q_start, q_start+count): pulls the
    per-matrix cotangents ``cts (B,)`` back through the rank range for a
    stack ``As (B, m, n)`` -> ``(B, m, n)``."""
    B, m, n = As.shape
    a = _grouped(As)
    G = a.shape[0]
    c = jnp.pad(cts.astype(jnp.float32),
                (0, G * SUBLANES - B)).reshape(G, SUBLANES, 1)
    out = _pallas_tiles(
        functools.partial(radic_batched_grad_combo_kernel, n, m, tile, G),
        padded_count, tile, table, q_start, count, [a, c],
        (G, SUBLANES * m, n), interpret, "radic_batched_grad_partial_pallas")
    return _ungrouped(out, B, m, n).astype(As.dtype)
