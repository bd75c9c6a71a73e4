"""Combinatorial addition — rank-addressable enumeration of ascending sequences.

This is the paper's core contribution (Section 4, Theorem 2): map an
arbitrary rank ``q`` in ``[0, C(n, m))`` to the ``q``-th ``m``-subset of
``{1..n}`` in dictionary (lexicographic) order, independently of all other
ranks, in ``O(m (n-m))`` time.

Three implementations, all proven equal in tests:

* :func:`unrank_py` / :func:`rank_py` / :func:`successor_py` — exact host
  Python (bigints, no width limit).  Used for grain starts in the
  distributed mode and as the oracle.
* :func:`unrank_jnp` — batched, fully vectorized JAX version.  The walk is
  *lane-uniform in the candidate value* ``v``: one ``fori_loop`` of exactly
  ``n`` steps, per-lane state is only (position ``i``, remaining ``q``).
  This is the TPU-native shape of the paper's PRAM per-processor loop.
* :func:`repro.kernels.common.unrank_lanes` — same walk, run inside the
  fused Pallas kernels on the lane rows of a rank tile in VMEM; at B = 1
  only on each lane's run start, which
  :func:`repro.kernels.common.successor_lanes` (the lane form of
  :func:`successor_jnp`) then walks.

Conventions: combinations are **1-indexed** ascending tuples, matching the
paper (``B_0 = [1, 2, .., m]``).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .pascal import binom_table, comb

__all__ = [
    "first_member",
    "last_member",
    "unrank_py",
    "rank_py",
    "successor_py",
    "unrank_jnp",
    "rank_jnp",
    "successor_jnp",
]


# --------------------------------------------------------------------------
# Host (exact, bigint) reference path — also the grain-start generator.
# --------------------------------------------------------------------------

def first_member(m: int) -> tuple[int, ...]:
    return tuple(range(1, m + 1))


def last_member(n: int, m: int) -> tuple[int, ...]:
    return tuple(range(n - m + 1, n + 1))


def unrank_py(q: int, n: int, m: int) -> tuple[int, ...]:
    """Exact unranking with Python ints (no overflow)."""
    if not 0 <= q < comb(n, m):
        raise ValueError(f"rank {q} outside [0, C({n},{m}))")
    out = []
    v = 1
    for i in range(m):  # position i gets the smallest feasible value
        while True:
            cnt = comb(n - v, m - 1 - i)
            if q < cnt:
                out.append(v)
                v += 1
                break
            q -= cnt
            v += 1
    return tuple(out)


def rank_py(combo: Sequence[int], n: int, m: int) -> int:
    """Inverse of :func:`unrank_py` (dictionary-order rank, exact)."""
    combo = tuple(combo)
    if len(combo) != m or any(c < 1 or c > n for c in combo):
        raise ValueError(f"not an m-subset of 1..{n}: {combo}")
    if any(a >= b for a, b in zip(combo, combo[1:])):
        raise ValueError(f"not ascending: {combo}")
    q = 0
    prev = 0
    for i, c in enumerate(combo):
        # hockey-stick: sum_{v=prev+1}^{c-1} C(n-v, m-1-i)
        q += comb(n - prev, m - i) - comb(n - c + 1, m - i)
        prev = c
    return q


def successor_py(combo: Sequence[int], n: int) -> tuple[int, ...] | None:
    """Next combination in dictionary order (None past the last member).

    This is the paper's per-grain enumeration step (second listing of
    Fig. 1): find the rightmost place below its cap, bump it, reset the
    suffix to a consecutive run.
    """
    b = list(combo)
    m = len(b)
    for i in range(m - 1, -1, -1):
        if b[i] < n - m + i + 1:
            b[i] += 1
            for j in range(i + 1, m):
                b[j] = b[j - 1] + 1
            return tuple(b)
    return None


# --------------------------------------------------------------------------
# Vectorized JAX path.
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "m"))
def unrank_jnp(qs: jax.Array, n: int, m: int, table: jax.Array | None = None
               ) -> jax.Array:
    """Batched unranking: ``qs (B,) int -> combos (B, m) int32`` (1-indexed).

    Vectorized form of the paper's combinatorial-addition walk.  The
    candidate value ``v`` advances ``1..n`` uniformly across lanes (one
    ``fori_loop`` of ``n`` steps); each lane keeps only its current
    position ``i`` and remaining rank.  Lane ``b`` places ``v`` at
    position ``i_b`` iff ``q_b < C(n - v, m - 1 - i_b)``.

    ``table`` lets callers pass a precomputed :func:`binom_table` (required
    inside traced code where ``n, m`` are static anyway).
    """
    if table is None:
        # convenience path (guarded callers pass a table): binom_table's
        # internal peak check bounds this build; importing the engine's
        # validate_rank_space here would cycle engine -> radic -> unrank
        dt = np.int64 if jax.config.jax_enable_x64 else np.int32
        table = jnp.asarray(binom_table(n, m, dtype=dt))  # reprolint: disable=overflow-guard
    qs = jnp.asarray(qs)
    # derive loop state from qs so shard_map varying-axis types propagate
    pos0 = (qs * 0).astype(jnp.int32)
    combo0 = jnp.broadcast_to(pos0[:, None], (qs.shape[0], m))
    cols = jnp.arange(m, dtype=jnp.int32)

    def step(s, carry):
        pos, q_rem, combo = carry
        v = s + 1  # candidate value, uniform across lanes
        row = table[n - v]  # (m+1,) counts C(n-v, *)
        col = jnp.clip(m - 1 - pos, 0, m)
        cnt = jnp.take(row, col)
        active = pos < m
        place = active & (q_rem < cnt)
        combo = jnp.where(place[:, None] & (cols[None, :] == pos[:, None]),
                          v, combo)
        q_rem = jnp.where(active & ~place, q_rem - cnt, q_rem)
        pos = pos + place.astype(jnp.int32)
        return pos, q_rem, combo

    _, _, combo = jax.lax.fori_loop(0, n, step, (pos0, qs, combo0))
    return combo


@functools.partial(jax.jit, static_argnames=("n", "m"))
def rank_jnp(combos: jax.Array, n: int, m: int,
             table: jax.Array | None = None) -> jax.Array:
    """Batched rank: ``combos (B, m) -> (B,)`` (dtype follows the table)."""
    if table is None:
        # convenience path: same justification as unrank_jnp above
        dt = np.int64 if jax.config.jax_enable_x64 else np.int32
        table = jnp.asarray(binom_table(n, m, dtype=dt))  # reprolint: disable=overflow-guard
    prevs = jnp.concatenate(
        [jnp.zeros_like(combos[:, :1]), combos[:, :-1]], axis=1)
    ks = m - jnp.arange(m, dtype=combos.dtype)  # m-i for i=0..m-1
    # contribution_i = C(n - prev_i, m - i) - C(n - c_i + 1, m - i)
    t_hi = table[(n - prevs), ks[None, :]]
    t_lo = table[(n - combos + 1), ks[None, :]]
    return jnp.sum(t_hi - t_lo, axis=1)


@functools.partial(jax.jit, static_argnames=("n",))
def successor_jnp(combos: jax.Array, n: int) -> jax.Array:
    """Batched dictionary-order successor, fully vectorized (no loop).

    ``combos (B, m) -> (B, m)``.  The last member maps to itself (callers
    mask by grain length).
    """
    B, m = combos.shape
    idx = jnp.arange(m, dtype=combos.dtype)
    caps = n - m + idx + 1  # max value allowed at each place
    can = combos < caps[None, :]
    any_can = jnp.any(can, axis=1)
    # last True index per lane
    i_star = (m - 1) - jnp.argmax(can[:, ::-1].astype(jnp.int32), axis=1)
    base = jnp.take_along_axis(combos, i_star[:, None], axis=1)  # (B, 1)
    bumped = base + 1 + (idx[None, :] - i_star[:, None])
    nxt = jnp.where(idx[None, :] < i_star[:, None], combos, bumped)
    return jnp.where(any_can[:, None], nxt, combos).astype(combos.dtype)
