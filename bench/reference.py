"""The plain reference for the Radic determinant, independent of the
program under test.

Radic's determinant of an m×n matrix A (m ≤ n) is

    det(A) = Σ_J (−1)^(r + s_J) · det(A[:, J]),   r = m(m+1)/2,

over every m-subset J of the columns (1-indexed), s_J the sum of J.
With D = diag((−1)^j) the sign folds into the columns: the sum is
(−1)^r times the sum of all maximal minors of B = A·D.  That sum is
computed here by a dynamic programme over the rows instead of an
enumeration of C(n, m) minors: walking the columns left to right, state
S (a set of rows) holds the signed sum over every way to give the rows
of S, in some order, to |S| increasing columns seen so far — the
Leibniz expansion of each minor, grouped.  Giving row i the next column
adds (−1)^#{k ∈ S : k > i} to the permutation's sign.  That is
O(n · m · 2^m) work: about 1.6 million multiply-adds at (12, 32),
against 2.6e11 for the enumeration.

The gradient follows from multilinearity in the rows: ∂det/∂A[i, j]
is the Radic determinant of A with row i replaced by the unit row e_j.

The term size of the sum, SCALE = sqrt(Σ_J det(A[:, J])²), is exact by
Cauchy–Binet: Σ_J det(A[:, J])² = det(A·Aᵀ).  Rounding errors of any
implementation follow the terms, not the (possibly cancelling) sum, so
errors are measured in units of SCALE.

Every function takes ``xp`` (numpy or jax.numpy) and a dtype, so the
same code is the float64 reference on the host and, with operands in
bfloat16, the control that the comparison has to reject.
"""

from __future__ import annotations

import functools

import numpy as np

__all__ = ["radic_dp", "radic_grad_dp", "radic_scale", "grad_scale",
           "brute_force", "to_bfloat16", "control_values", "control_grads"]


@functools.lru_cache(maxsize=None)
def _moves(m: int):
    """For each row i: the source state T \\ {i} of every state T (or the
    zero slot 2^m when i ∉ T), and the sign that giving row i its column
    adds to the permutation (0 when i ∉ T)."""
    size = 1 << m
    src = np.full((m, size), size, np.int64)
    sgn = np.zeros((m, size), np.float64)
    for i in range(m):
        for t in range(size):
            if t >> i & 1:
                s = t & ~(1 << i)
                src[i, t] = s
                sgn[i, t] = -1.0 if bin(s >> (i + 1)).count("1") & 1 else 1.0
    return src, sgn


def radic_dp(As, xp=np, dtype=np.float64):
    """Radic determinants of a stack ``As (B, m, n)`` -> ``(B,)``."""
    As = xp.asarray(As, dtype)
    B, m, n = As.shape
    if m > n:
        return xp.zeros((B,), dtype)
    src, sgn = _moves(m)
    cols = xp.asarray(np.where(np.arange(1, n + 1) % 2, -1.0, 1.0), dtype)
    Bm = As * cols[None, None, :]
    size = 1 << m
    state = xp.concatenate([xp.ones((B, 1), dtype),
                            xp.zeros((B, size), dtype)], axis=1)
    sgn_x = xp.asarray(sgn, dtype)
    for j in range(n):
        new = state[:, :size]
        for i in range(m):
            new = new + (xp.take(state, xp.asarray(src[i]), axis=1)
                         * sgn_x[i][None, :] * Bm[:, i, j][:, None])
        state = xp.concatenate([new, xp.zeros((B, 1), dtype)], axis=1)
    r = m * (m + 1) // 2
    return state[:, size - 1] * (-1.0 if r & 1 else 1.0)


def _unit_rows(As):
    """``(B, m, n)`` -> ``(B·m·n, m, n)``: A with row i replaced by e_j,
    in (b, i, j) order."""
    As = np.asarray(As)
    B, m, n = As.shape
    out = np.repeat(As[:, None, None], m * n, axis=1).reshape(B, m, n, m, n)
    ii, jj = np.meshgrid(np.arange(m), np.arange(n), indexing="ij")
    out[:, ii, jj, ii, :] = 0.0
    out[:, ii, jj, ii, jj] = 1.0
    return out.reshape(B * m * n, m, n)


def radic_grad_dp(As, xp=np, dtype=np.float64):
    """∂det/∂A for a stack ``As (B, m, n)`` -> ``(B, m, n)``."""
    B, m, n = np.shape(As)
    return xp.reshape(radic_dp(_unit_rows(As), xp, dtype), (B, m, n))


def radic_scale(As) -> np.ndarray:
    """SCALE = sqrt(det(A·Aᵀ)) per matrix, float64 -> ``(B,)``."""
    A = np.asarray(As, np.float64)
    g = np.linalg.det(A @ np.swapaxes(A, 1, 2))
    return np.sqrt(np.maximum(g, 0.0))


def grad_scale(As) -> np.ndarray:
    """The term size of each gradient entry -> ``(B, m, n)``."""
    B, m, n = np.shape(As)
    return radic_scale(_unit_rows(As)).reshape(B, m, n)


def to_bfloat16(As) -> np.ndarray:
    """Operands rounded to bfloat16, as a default-precision MXU pass
    rounds them, and widened back to float32."""
    import ml_dtypes
    return np.asarray(As, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def brute_force(A) -> float:
    """Definition 3 term by term, float64: the check of the check, for
    small shapes only."""
    from itertools import combinations
    A = np.asarray(A, np.float64)
    m, n = A.shape
    r = m * (m + 1) // 2
    total = 0.0
    for J in combinations(range(n), m):
        s = sum(J) + m      # 1-indexed column sum
        total += (-1) ** (r + s) * np.linalg.det(A[:, list(J)])
    return total


def control_values(As, xp=np) -> np.ndarray:
    """The control: the reference in the program's place, with its
    operands in bfloat16 and float32 arithmetic."""
    return np.asarray(radic_dp(to_bfloat16(As), xp, np.float32),
                      np.float64)


def control_grads(As, xp=np) -> np.ndarray:
    """The control's gradients, at the same precision."""
    return np.asarray(radic_grad_dp(to_bfloat16(As), xp, np.float32),
                      np.float64)
