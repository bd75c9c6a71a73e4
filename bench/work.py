"""Algorithmic work of the Radic determinant, from shapes alone.

What the algorithm needs, not what an implementation spends: the
Gaussian elimination of each m×m minor (⅔m³ flops) plus the sign and
the accumulation (2 flops), for every one of the C(n, m) minors of every
real matrix.  Padded batch rows and the one-hot gather are choices of
the implementation and do not count.  Bytes: the matrix is read once.
"""

from __future__ import annotations

import json
import math
import os

__all__ = ["flops_per_minor", "det_flops", "det_bytes", "peaks",
           "roofline_seconds"]

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def flops_per_minor(m: int) -> float:
    """⅔m³ for the elimination, 2 for the sign and the accumulation."""
    return 2.0 * m ** 3 / 3.0 + 2.0


def det_flops(m: int, n: int, matrices: int = 1) -> float:
    """Flops of ``matrices`` Radic determinants of m×n matrices."""
    if m > n:
        return 0.0
    return matrices * math.comb(n, m) * flops_per_minor(m)


def det_bytes(m: int, n: int, matrices: int = 1) -> float:
    """HBM bytes the algorithm must move: each f32 matrix read once and
    its f32 result written once."""
    return matrices * 4.0 * (m * n + 1)


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown device is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {PEAKS_FILE}")
    return table[device_kind]


def roofline_seconds(flops: float, nbytes: float, device_kind: str):
    """(least seconds the chip could take, which bound sets it)."""
    p = peaks(device_kind)
    t_c = flops / p["flops_per_s"]
    t_m = nbytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
