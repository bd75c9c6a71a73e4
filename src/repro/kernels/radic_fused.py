"""Fused Radic-partial Pallas kernels: forward and cofactor-form backward.

One kernel = the paper's whole per-processor pipeline, fused so minors
never touch HBM:

    B ≥ 2: rank tile ──unrank (VPU, n static steps)──► combos, m (1, T)
    B = 1: run start ──unrank once, at step 0────────► combos, m (8, T)
                     ──successor per step (VPU, ~7m)─► next combos
           combos    ──one-hot matmuls (MXU)─────────► minor slabs (8, T)
                     ──pivoted GE (VPU, unrolled in m)► dets (8, T)
                     ──sign · mask───────────────────► per-lane partials

Two layouts of the 8 f32 sublanes, chosen from the static batch size
(:func:`rank_rows`), and with them the rank walk (:func:`rank_walk`):

* B ≥ 2, matrices on sublanes (R = 1): the T ranks of a tile sit on the
  128-wide lane axis and the batch on the sublanes, 8 matrices per
  group.  A grid step unranks its tile once, builds the selectors and
  signs once, then walks the batch group by group, so that shared work
  is paid once per tile and not once per matrix.  The batch enters the
  kernel group-major, ``(G, 8·m, n)`` with row ``a·8 + s`` of group g
  holding row a of matrix ``8g + s``; the wrappers pad B up to a
  multiple of 8 with zero matrices, whose results are sliced off.
* B = 1, rank rows on sublanes (R = 8): each of the 8T lanes ``l = s·T
  + t`` owns one contiguous run of ``K = ceil(count / 8T)`` ranks of the
  one matrix (:func:`lane_runs`) and handles the run's ``pid``-th rank
  at grid step ``pid``, so a step covers 8T ranks and no sublane
  computes a zero matrix.  As in the paper's per-processor algorithm, a
  lane unranks its run's start once, at step 0, into VMEM scratch that
  persists across the sequential grid, and every step moves it on by
  the dictionary successor (``common.successor_lanes``).  The matrix
  enters as its exact bf16 parts side by side, ``(1, m, 3·np)``, and
  each rank row takes one bf16 one-hot matmul whose f32 outputs are A's
  entries bit for bit (``common.rank_row_slabs``); the elimination reads
  the slabs.  B ≥ 2 and the gradient gather f32 at
  ``Precision.HIGHEST``.

``q_start``, then the whole steps and the tail of ``count`` (B ≥ 2 and
the gradient) or ``count`` and the run length K (B = 1), and the
flattened Pascal table are scalar-prefetch operands (SMEM).  The
outputs stay resident across the sequential grid: the forward adds its
masked per-lane terms into a ``(G, 8, T)`` block that the wrapper sums
over lanes (and over sublanes at B = 1), the backward adds
``(G, 8·m, n)`` gradient partials.  HBM input per tile is zero beyond
the resident A and table — ranks are generated from the grid index.
The rank-window kernel (``radic_windowed.py``) runs the B = 1 layout
and its walk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (det_slabs, minor_slabs, rank_row_operand,
                     rank_row_slabs, selector_slabs, sign_lanes,
                     successor_lanes, unrank_lanes)

__all__ = ["radic_partial_pallas", "radic_batched_combo_kernel",
           "radic_batched_partial_pallas",
           "radic_batched_grad_combo_kernel",
           "radic_batched_grad_partial_pallas", "rank_rows", "rank_walk",
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


def rank_walk(rows: int | None) -> str | None:
    """How the value kernel of :func:`rank_rows` ``rows`` finds each
    lane's combination, its engagement record: ``"successor"`` where a
    lane walks its own run of ranks (8 rank rows, B = 1 and the windowed
    kernel: :func:`lane_runs`), ``"unrank"`` where every grid step
    unranks its tile (1 row, B ≥ 2), ``None`` without a kernel."""
    if rows is None:
        return None
    return "successor" if rows > 1 else "unrank"


def _steps(count, step: int) -> list[jax.Array]:
    """``count`` ranks as whole grid steps of ``step`` ranks and a tail:
    the scalars :func:`lane_ranks` reads, so no rank offset in the kernel
    is ever formed past ``count``."""
    count = jnp.asarray(count, jnp.int32)
    return [count // step, count % step]


def lane_ranks(pid, q_start, full, tail, tile: int):
    """Validity mask and ranks ``(1, tile)`` of grid step ``pid``, for
    ``count = full·tile + tail`` ranks from ``q_start``: lane t is rank
    ``q_start + pid·tile + t`` where that lies inside the range.

    Every offset is bounded by ``count``: the base is taken at
    ``min(pid, full)`` steps and a masked lane adds nothing, so a grid
    that runs past the range — the padded grid extent, or a shard's
    shorter grain — forms no offset that could wrap int32.  A masked lane
    unranks a rank of ``[q_start, q_start + count]`` and is dropped."""
    local = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    limit = jnp.where(pid < full, tile, jnp.where(pid == full, tail, 0))
    valid = local < limit
    base = jnp.minimum(pid, full) * tile
    return valid, q_start + base + jnp.where(valid, local, 0)


def _runs(count, lanes: int) -> list[jax.Array]:
    """``count`` ranks cut into one run per lane of ``lanes`` lanes: the
    count and the run length ``K = ceil(count / lanes)``, the scalars
    :func:`lane_runs` reads, formed without passing ``count``."""
    count = jnp.asarray(count, jnp.int32)
    return [count, count // lanes + (count % lanes > 0).astype(jnp.int32)]


def lane_runs(pid, count, run, tile: int):
    """The run layout of 8 rank rows: lane ``l = s·tile + t`` of an
    ``(8, tile)`` slab owns the ranks ``[l·K, l·K + K)`` of a range of
    ``count`` ranks, ``K = run``, clipped to the range, and handles the
    ``pid``-th of them at grid step ``pid``.  Returns the validity mask of
    that step and each lane's start offset, which it unranks once.

    No offset wraps int32: ``l·K < 8·tile·K``, which for a power-of-two
    tile is at most 2³¹ for any ``count`` under it; validity compares
    ``pid`` with ``count − l·K`` instead of forming ``l·K + pid``; and a
    lane whose run starts at or past the range's end starts at its last
    rank, ``count − 1``, and is masked at every step."""
    lane = (jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, tile), 0) * tile
            + jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, tile), 1))
    first = lane * run
    valid = (pid < run) & (pid < count - first)
    return valid, jnp.minimum(first, jnp.maximum(count - 1, 0))


def walk_run_step(n: int, m: int, parts, combo_ref, valid, out_ref):
    """One grid step of the run layout: the lanes' combinations from
    ``combo_ref`` (m ``(8, T)`` int32 rows, VMEM scratch that persists
    across the sequential grid), their gather from A's bf16 ``parts``,
    elimination and masked signed terms into ``out_ref[0]``, then each
    lane's successor back into ``combo_ref`` for the next step."""
    combos = [combo_ref[k] for k in range(m)]
    dets = det_slabs(rank_row_slabs(parts, combos))
    out_ref[0] += jnp.where(valid, sign_lanes(combos, m) * dets, 0.0)
    for k, c in enumerate(successor_lanes(combos, n)):
        combo_ref[k] = c


def _tile_setup(n: int, m: int, tile: int, qinfo_ref, table_ref):
    """The rank tile of this grid step, shared by every matrix of the
    batch: lane validity mask, combinations and signs, ``(1, tile)``."""
    valid, qs = lane_ranks(pl.program_id(0), qinfo_ref[0], qinfo_ref[1],
                           qinfo_ref[2], tile)
    # in-kernel unranking; guarded at the ops.py entry points
    combos = unrank_lanes(qs, n, m, table_ref)  # reprolint: disable=overflow-guard
    return valid, combos, sign_lanes(combos, m)


def radic_batched_combo_kernel(n: int, m: int, tile: int, rows: int,
                               groups: int, qinfo_ref, table_ref, a_ref,
                               out_ref, *scratch):
    """Forward: per-lane Σ sign·det of this tile for every matrix, or at
    ``rows`` = 8 the next rank of each lane's run of the one matrix, from
    its bf16 parts; ``scratch`` is then the runs' VMEM combinations."""
    pid = pl.program_id(0)

    @pl.when(pid == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    if rows > 1:
        (combo_ref,) = scratch
        valid, start = lane_runs(pid, qinfo_ref[1], qinfo_ref[2], tile)

        @pl.when(pid == 0)
        def _unrank_starts():
            # in-kernel unranking; guarded at the ops.py entry points
            combos = unrank_lanes(  # reprolint: disable=overflow-guard
                qinfo_ref[0] + start, n, m, table_ref)
            for k, c in enumerate(combos):
                combo_ref[k] = c

        walk_run_step(n, m, a_ref[0], combo_ref, valid, out_ref)
        return
    valid, combos, signs = _tile_setup(n, m, tile, qinfo_ref, table_ref)
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
    valid, combos, signs = _tile_setup(n, m, tile, qinfo_ref, table_ref)
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
                  scalars, operands, out_shape, interpret, name: str,
                  scratch=()):
    """One sequential grid step per ``step`` ranks; every operand and the
    output are whole-array blocks resident in VMEM, ``q_start`` and the
    range's ``scalars`` (:func:`_steps` or :func:`_runs`) are in SMEM and
    ``scratch`` is VMEM that persists across the grid.  ``name`` is the HLO
    instruction's name, which a device trace shows and the benchmark's
    kernel readers match (``tests/test_tpu_compile.py`` pins it)."""

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(max(1, -(-padded_count // step)),),
            in_specs=[whole(x.shape) for x in operands],
            out_specs=whole(out_shape), scratch_shapes=list(scratch)),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
        name=name,
    )(jnp.stack([jnp.asarray(q_start, jnp.int32), *scalars]),
      table.astype(jnp.int32).reshape(-1), *operands)


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
    puts 8 rank rows of the one matrix on the sublanes (:func:`rank_rows`),
    each lane walking its own run of ranks, and a grid step covers
    ``8·tile`` ranks."""
    B, m, n = As.shape
    rows = rank_rows(B)
    a = _grouped(As) if rows == 1 else rank_row_operand(As[0])[None]
    G = a.shape[0]
    step = rows * tile
    if rows > 1:
        scalars = _runs(count, step)
        scratch = [pltpu.VMEM((m, SUBLANES, tile), jnp.int32)]
    else:
        scalars, scratch = _steps(count, step), []
    out = _pallas_tiles(
        functools.partial(radic_batched_combo_kernel, n, m, tile, rows, G),
        padded_count, step, table, q_start, scalars, [a],
        (G, SUBLANES, tile), interpret, "radic_batched_partial_pallas",
        scratch)
    if rows > 1:  # the sublanes first: a range under one row keeps its sum
        out = out.sum(axis=1)
    return out.reshape(-1, tile)[:B].sum(axis=1).astype(As.dtype)


def radic_partial_pallas(A: jax.Array, table: jax.Array,
                         q_start: jax.Array | int, count: jax.Array | int,
                         padded_count: int, *, tile: int = 256,
                         interpret: bool | None = None) -> jax.Array:
    """Σ sign·det over ranks [q_start, q_start+count) of one ``A (m, n)``:
    the batched kernel at B = 1, 8 rank rows per grid step, a run of
    ranks per lane."""
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
        padded_count, tile, table, q_start, _steps(count, tile), [a, c],
        (G, SUBLANES * m, n), interpret, "radic_batched_grad_partial_pallas")
    return _ungrouped(out, B, m, n).astype(As.dtype)
