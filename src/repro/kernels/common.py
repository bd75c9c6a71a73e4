"""Shared in-kernel routines, lane-major.

The rank tile lives on the 128-wide lane axis: ``T`` ranks form one
``(1, T)`` row shared by the matrices of a batch, or ``(S, T)`` rows
when one matrix fills the sublanes with S rank rows of its own.  A
per-minor quantity is an ``(S, T)`` slab, one matrix and rank per
(sublane, lane).  An m×m matrix is a python list of m·m slabs, so the
Gaussian elimination, the products and the row swaps are static unrolls
over the small m of elementwise ops on whole slabs.  The whole set of
constructs is what Mosaic lowers: elementwise ops, plain 2-D matmuls
without batch dims, static slices and scalar table reads — no
``dynamic_slice``, no batched ``dot_general``, no ``reduce_prod``.

The same functions run as ordinary jnp inside jit, so the fused kernels,
their CPU interpret mode and the ``(T, …)``-layout adapters at the bottom
share one implementation of the math.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["det_slabs", "unrank_lanes", "selector_slabs", "minor_slabs",
           "rank_row_slabs", "sign_lanes", "unrank_window_lanes",
           "batched_det_ge", "unrank_tile", "onehot_gather_minors",
           "radic_signs"]


def det_slabs(M: list[list[jax.Array]]) -> jax.Array:
    """Determinants of m×m matrices held as slabs: ``M[r][c]`` is an
    ``(S, T)`` slab, one matrix per (sublane, lane).  Returns ``(S, T)``.

    Gaussian elimination with partial pivoting, vectorized over every
    slab element — the paper's reference [7] PRAM determinant replaced by
    lane parallelism.  The pivot is the first row of largest magnitude
    (``argmax`` semantics).  A zero pivot leaves a zero on the diagonal,
    so a singular minor gets det 0, the exact answer.  Columns left of
    the pivot are never read again, so swaps and updates skip them.
    """
    m = len(M)
    M = [list(row) for row in M]
    sign = jnp.ones_like(M[0][0])
    for k in range(m - 1):
        best = jnp.abs(M[k][k])
        piv = jnp.full(best.shape, k, jnp.int32)
        for r in range(k + 1, m):
            cand = jnp.abs(M[r][k])
            better = cand > best
            best = jnp.where(better, cand, best)
            piv = jnp.where(better, r, piv)
        sign = jnp.where(piv == k, sign, -sign)
        hits = [piv == r for r in range(k + 1, m)]
        for c in range(k, m):
            top = M[k][c]
            for r, hit in zip(range(k + 1, m), hits):
                M[k][c] = jnp.where(hit, M[r][c], M[k][c])
                M[r][c] = jnp.where(hit, top, M[r][c])
        pivval = M[k][k]
        safe = jnp.where(pivval == 0, jnp.ones_like(pivval), pivval)
        for r in range(k + 1, m):
            f = M[r][k] / safe
            for c in range(k + 1, m):
                M[r][c] = M[r][c] - f * M[k][c]
    det = sign
    for k in range(m):
        det = det * M[k][k]
    return det


def unrank_lanes(qs: jax.Array, n: int, m: int, table) -> list[jax.Array]:
    """Combinatorial addition on a lane row: ranks ``qs (S, T)`` int32 ->
    the 1-indexed combination as m ``(S, T)`` rows (element k of every
    lane's combination in row k).

    ``table`` is the flattened Pascal table, ``table[i * (m + 1) + j] =
    C(i, j)``, read one scalar at a time: an SMEM ref inside a kernel, an
    array elsewhere.  Step v looks up ``C(n - v, m - 1 - pos)`` per lane
    as a select over the m positions a lane can be at.
    """

    def step(v, carry):
        pos, q, combo = carry
        row = (n - v) * (m + 1) + m - 1
        at = [pos == p for p in range(m)]
        cnt = jnp.zeros_like(q)
        for p in range(m):
            cnt = jnp.where(at[p], table[row - p], cnt)
        active = pos < m
        place = active & (q < cnt)
        combo = tuple(jnp.where(place & at[p], v, c)
                      for p, c in enumerate(combo))
        q = jnp.where(active & ~place, q - cnt, q)
        return pos + place.astype(pos.dtype), q, combo

    zero = jnp.zeros_like(qs)
    _, _, combo = jax.lax.fori_loop(1, n + 1, step,
                                    (zero, qs, (zero,) * m))
    return list(combo)


def selector_slabs(combos: list[jax.Array], n: int) -> list[jax.Array]:
    """One-hot column selectors of a tile: m ``(1, T)`` 1-indexed rows ->
    m ``(n, T)`` f32 slabs, ``sel[k][j, t] = (combos[k][t] == j + 1)``.
    They depend on the rank tile only, so a kernel builds them once and
    gathers every matrix of its batch with them."""
    T = combos[0].shape[1]
    jidx = jax.lax.broadcasted_iota(jnp.int32, (n, T), 0)
    return [(jidx == c - 1).astype(jnp.float32) for c in combos]


def minor_slabs(A: jax.Array, sel: list[jax.Array]
                ) -> list[list[jax.Array]]:
    """Column gather as MXU matmuls: ``A (m·S, n)`` holds row a of matrix
    s at row ``a·S + s``; ``sel`` are the m selectors of
    :func:`selector_slabs`.

    Returns slabs ``M[k][a] (S, T) = A_s[a, combos[k] - 1]`` — the
    transposed minor, which has the same determinant.  Each k is one
    ``(m·S, n) @ (n, T)`` one-hot matmul at full f32 precision, so the
    selected entries are exact.
    """
    m = len(sel)
    S = A.shape[0] // m
    M = []
    for oh in sel:
        cols = jax.lax.dot(A, oh, precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
        M.append([cols[a * S:(a + 1) * S] for a in range(m)])
    return M


def rank_row_slabs(A: jax.Array, combos: list[jax.Array], n: int
                   ) -> list[list[jax.Array]]:
    """Column gather for one matrix whose sublanes hold S rows of ranks:
    ``A (m, n)``, ``combos`` m ``(S, T)`` rows -> slabs ``M[k][a] (S, T)
    = A[a, combos[k][s, t] - 1]``, the transposed minors of every rank,
    as :func:`minor_slabs` lays them out.

    The selectors differ per rank row, so each k takes S one-hot matmuls
    ``(m, n) @ (n, T)`` at full f32 precision, exact as in
    :func:`minor_slabs`, and row a of the S products fills the sublanes
    of ``M[k][a]``."""
    S = combos[0].shape[0]
    M = []
    for c in combos:
        sel = selector_slabs([c[s:s + 1] for s in range(S)], n)
        cols = [jax.lax.dot(A, oh, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
                for oh in sel]
        M.append([jnp.concatenate([r[a:a + 1] for r in cols], axis=0)
                  for a in range(A.shape[0])])
    return M


def sign_lanes(combos: list[jax.Array], m: int) -> jax.Array:
    """(−1)^(r+s) per lane as f32, ``r = m(m+1)/2``, ``s`` the column sum."""
    s = combos[0]
    for c in combos[1:]:
        s = s + c
    parity = (s + m * (m + 1) // 2) & 1
    return (1 - 2 * parity).astype(jnp.float32)


def unrank_window_lanes(ds: jax.Array, n: int, m: int, table, slack,
                        base=0) -> list[jax.Array]:
    """Combinatorial addition relative to a window's start: local offsets
    ``ds (S, T)`` int32 of the window starting at rank q0 -> the
    combination of rank ``q0 + d`` as m ``(S, T)`` rows, as
    :func:`unrank_lanes` lays them out.  No lane forms a global rank.

    ``slack[base + v]`` is column v's entry of the window's
    ``core.engine.window_slacks``: S0 = unrank(q0)'s slack where S0
    places v, 0 where it skips v.  A lane starts on S0's path with
    ``q = d``.  On the path it places v where S0 does and ``q`` is under
    the slack, and otherwise leaves the path with ``q − slack``; off the
    path it runs :func:`unrank_lanes`' walk.  ``q`` stays under 2³¹, so
    ``table`` may be clipped at ``INT32_MAX``: a clipped entry only ever
    meets a ``q`` below it, and is never subtracted.
    """

    def step(v, carry):
        pos, q, on, combo = carry
        row = (n - v) * (m + 1) + m - 1
        at = [pos == p for p in range(m)]
        cnt = jnp.zeros_like(q)
        for p in range(m):
            cnt = jnp.where(at[p], table[row - p], cnt)
        cnt = jnp.where(on == 1, slack[base + v], cnt)
        active = pos < m
        place = active & (q < cnt)
        combo = tuple(jnp.where(place & at[p], v, c)
                      for p, c in enumerate(combo))
        q = jnp.where(active & ~place, q - cnt, q)
        # on the path, a column with slack 0 is one S0 skips: stay on it
        on = jnp.where(place | (cnt == 0), on, 0)
        return pos + place.astype(pos.dtype), q, on, combo

    zero = jnp.zeros_like(ds)
    _, _, _, combo = jax.lax.fori_loop(
        1, n + 1, step, (zero, ds, jnp.ones_like(ds), (zero,) * m))
    return list(combo)


# ------------------------------------------------- (T, …)-layout adapters
# The standalone minor_det/unrank kernels, the legacy by-grid batched
# kernel and the stage benchmark keep the rank-major ``(T, m)`` layout;
# they call the lane-major math above through these adapters.

def batched_det_ge(M: jax.Array) -> jax.Array:
    """``M (T, m, m) -> (T,)`` through :func:`det_slabs`."""
    T, m, m2 = M.shape
    assert m == m2, M.shape
    if m == 0:
        return jnp.ones((T,), M.dtype)
    slabs = [[M[:, r, c][None, :] for c in range(m)] for r in range(m)]
    return det_slabs(slabs)[0]


def unrank_tile(qs: jax.Array, n: int, m: int, table: jax.Array
                ) -> jax.Array:
    """``qs (T,) -> (T, m)`` 1-indexed, through :func:`unrank_lanes`;
    ``table`` is the ``(n + 1, m + 1)`` Pascal table."""
    rows = unrank_lanes(qs[None, :], n, m, table.reshape(-1))
    return jnp.concatenate(rows, axis=0).T


def onehot_gather_minors(A: jax.Array, combos: jax.Array) -> jax.Array:
    """Column gather as a matmul: ``A (m,n), combos (T,m) -> (T,m,m)``,
    the transposed minor (determinant-invariant)."""
    T, m = combos.shape
    jidx = jax.lax.broadcasted_iota(jnp.int32, (T, m, A.shape[1]), 2)
    oh = (combos[:, :, None] - 1 == jidx).astype(A.dtype)
    return jnp.einsum("tkn,an->tka", oh, A, preferred_element_type=A.dtype)


def radic_signs(combos: jax.Array, m: int, dtype=jnp.float32) -> jax.Array:
    """(−1)^(r+s) per row of ``combos (T, m)``."""
    r = m * (m + 1) // 2
    parity = (jnp.sum(combos, axis=1) + r) & 1
    return (1 - 2 * parity).astype(dtype)
