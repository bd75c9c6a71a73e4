"""Shared in-kernel routines, lane-major.

The rank tile lives on the 128-wide lane axis: ``T`` ranks form one
``(1, T)`` row shared by the matrices of a batch, or ``(S, T)`` rows
when one matrix fills the sublanes with S rank rows of its own.  A
per-minor quantity is an ``(S, T)`` slab, one matrix and rank per
(sublane, lane); an m×m matrix is a list of m·m slabs, and the Gaussian
elimination is a static unroll over m of elementwise ops on them, in
what Mosaic lowers: no ``dynamic_slice``, batched ``dot_general`` or
``reduce_prod``.  Column gathers are one-hot MXU matmuls whose entries
are A's exactly: f32 at ``Precision.HIGHEST`` (:func:`minor_slabs`), or
at B = 1 one bf16 pass over A's exact three-part split
(:func:`rank_row_slabs`, :func:`split_bf16x3`).  Both lose only what the
TPU flushes: an entry under about 2⁻¹⁰⁰ whose low part is subnormal.
The same functions run as ordinary jnp inside jit, so the fused kernels
and their CPU interpret mode share the math, and the tests call them
directly against the oracles of :mod:`repro.kernels.ref`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["det_slabs", "unrank_lanes", "successor_lanes",
           "selector_slabs", "minor_slabs", "rank_row_slabs", "sign_lanes",
           "unrank_window_lanes", "split_bf16x3", "rank_row_operand"]


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


def successor_lanes(combos: list[jax.Array], n: int) -> list[jax.Array]:
    """Each lane's next combination in dictionary order: m ``(S, T)``
    rows as :func:`unrank_lanes` lays them out -> the same of the
    successors.  The lane form of ``core.unrank.successor_jnp``, the
    paper's per-grain enumeration step: place i is below its cap when
    ``c_i < n − m + i + 1``; the rightmost such place is bumped and the
    places after it reset to a consecutive run, ``c_j = c_i* + 1 + j −
    i*``.  With ``d = c_i* − i*`` that is ``d + 1 + j`` for ``j ≥ i*``.
    The last member has no such place: ``i* = −1`` and ``d = n − m``
    give ``n − m + 1 + j``, itself, so it maps to itself; callers mask.
    """
    m = len(combos)
    istar = jnp.full_like(combos[0], -1)
    d = jnp.full_like(combos[0], n - m)
    for i, c in enumerate(combos):
        can = c < n - m + i + 1
        istar = jnp.where(can, i, istar)
        d = jnp.where(can, c - i, d)
    return [jnp.where(istar > j, c, d + (1 + j))
            for j, c in enumerate(combos)]


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


def rank_row_slabs(parts: jax.Array, combos: list[jax.Array]
                   ) -> list[list[jax.Array]]:
    """Column gather for one matrix whose sublanes hold S rows of ranks:
    ``parts`` its :func:`rank_row_operand`, ``combos`` m ``(S, T)`` rows ->
    slabs ``M[k][a] (S, T) = A[a, combos[k][s, t] - 1]`` bit for bit, as
    :func:`minor_slabs` lays them out.  Per k, S single-pass bf16 matmuls
    ``(m, 3·np) @ (3·np, T)`` against the one-hot stacked thrice: the f32
    accumulator adds the entry's hi, mid and lo, exact in any order
    (:func:`split_bf16x3`); row a of the S products fills ``M[k][a]``."""
    S, T = combos[0].shape
    jidx = jax.lax.broadcasted_iota(jnp.int32, (parts.shape[1] // 3, T), 0)
    M = []
    for c in combos:
        rows = []
        for s in range(S):
            oh = (jidx == c[s:s + 1] - 1).astype(jnp.bfloat16)
            rows.append(jax.lax.dot(parts, jnp.concatenate([oh, oh, oh]),
                                    preferred_element_type=jnp.float32))
        M.append([jnp.concatenate([r[a:a + 1] for r in rows], axis=0)
                  for a in range(len(combos))])
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


def split_bf16x3(A: jax.Array) -> jax.Array:
    """``A (…)`` f32 -> its three bf16 parts ``(3, …)``, hi, mid and lo,
    whose f32 sum is A exactly, in whichever order they are added.

    Significand truncation: hi is A with the low 16 bits of its f32 word
    cleared (sign, exponent and the top 7 stored bits), mid the same cut
    of ``A − hi``, and ``lo = A − hi − mid``.  Each subtraction is exact,
    each part holds its own 8 of A's 24 significant bits, so each is a
    bf16 value and the f32 sums of the parts lose nothing.  A zero part
    takes A's sign, so −0 re-adds to −0.  The one limit, shared with
    ``Precision.HIGHEST``'s own bf16 passes: the TPU flushes a subnormal
    part, so an entry under about 2⁻¹⁰⁰ may lose its low bits."""
    A = A.astype(jnp.float32)

    def trunc(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)

    hi = trunc(A)
    rest = A - hi
    mid = trunc(rest)
    lo = rest - mid
    return jnp.stack([jnp.copysign(p, A) for p in (hi, mid, lo)]
                     ).astype(jnp.bfloat16)


def rank_row_operand(A: jax.Array) -> jax.Array:
    """The gather operand of :func:`rank_row_slabs` for ``A (m, n)``: its
    three bf16 parts side by side ``(m, 3·np)``, part p in columns
    ``[p·np, p·np + n)`` and zeros after, np = n rounded up to the 16
    rows of a bf16 tile so that the one-hot stacks at tile boundaries."""
    m, n = A.shape
    np_ = -(-n // 16) * 16
    parts = jnp.pad(split_bf16x3(A), ((0, 0), (0, 0), (0, np_ - n)))
    return parts.transpose(1, 0, 2).reshape(m, 3 * np_)
