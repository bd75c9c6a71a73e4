"""The one general generator: turns a traffic mix (a data file under
``bench/traffic/``) and ``--seed`` into the requests of a run.

Every seed gets the same work: the same number of requests of each
shape, the same share of gradients and the same multiset of arrival
gaps, in an order and with matrix entries that the seed draws.  So two
seeds differ by the order and the values, not by how much there is to
do.

Mixes:

* ``solve`` — back-to-back single determinants of the configuration's
  shape, each matrix distinct (:class:`SolveStream`);
* ``open`` — open-loop arrivals at a fixed rate, Poisson gaps
  (:func:`open_schedule`);
* ``closed`` — a fixed number of requests kept in flight
  (:class:`ClosedStream`).
"""

from __future__ import annotations

import math

import numpy as np

__all__ = ["rng_for", "head_shapes", "mix_shapes", "SolveStream",
           "OpenSchedule", "open_schedule", "ClosedStream"]


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed."""
    return np.random.default_rng([seed % 2 ** 64, *stream])


def head_shapes(max_m: int = 7, target_ranks: int = 15000,
                per_m: int = 3) -> list[tuple[int, int]]:
    """An equal-work hot-shape set: for each row count m ≥ 3, the first
    ``per_m`` column widths whose rank space C(n, m) lands within
    [0.7x, 1.6x] of ``target_ranks``.  Copied from the serving
    benchmark of this repository (``benchmarks/perf_serve.py``)."""
    lo, hi = int(target_ranks * 0.7), int(target_ranks * 1.6)
    shapes = []
    for m in range(3, max_m + 1):
        found = 0
        for n in range(m, 80):
            c = math.comb(n, m)
            if c > hi:
                break
            if c >= lo:
                shapes.append((m, n))
                found += 1
                if found >= per_m:
                    break
    return shapes


def mix_shapes(spec) -> list[tuple[int, int]]:
    """A mix's shapes: an explicit ``[[m, n], ...]`` list, or a family
    ``{"family": "head", ...keyword arguments}``."""
    if isinstance(spec, dict):
        kw = {k: v for k, v in spec.items() if k != "family"}
        if spec.get("family") != "head":
            raise ValueError(f"unknown shape family {spec.get('family')!r}")
        return head_shapes(**kw)
    return [(int(m), int(n)) for m, n in spec]


def _spread(total: int, parts: int) -> np.ndarray:
    """``total`` items over ``parts`` classes as evenly as possible, the
    remainder to the first classes, the same for every seed."""
    counts = np.full(parts, total // parts)
    counts[:total % parts] += 1
    return counts


class SolveStream:
    """The k-th matrix of a back-to-back solve loop, drawn from the seed:
    a standard normal float32 matrix of the configuration's shape."""

    def __init__(self, shape, seed: int):
        self.shape = tuple(shape)
        self.seed = seed
        self.count = 0

    def matrix(self, k: int) -> np.ndarray:
        return rng_for(self.seed, 1, k).standard_normal(
            self.shape, np.float32)

    def next(self) -> np.ndarray:
        A = self.matrix(self.count)
        self.count += 1
        return A


class OpenSchedule:
    """Requests of an open-loop window: ``due`` seconds after the window
    opens, the matrix, and whether it asks for the gradient."""

    def __init__(self, due, mats, grads, shapes, shape_idx):
        self.due = due
        self.mats = mats
        self.grads = grads
        self.shapes = shapes
        self.shape_idx = shape_idx

    def __len__(self):
        return len(self.due)


def _matrices(shapes, shape_idx, rng) -> list[np.ndarray]:
    """Per-request standard normal matrices, drawn in bulk per shape."""
    mats: list = [None] * len(shape_idx)
    for s, (m, n) in enumerate(shapes):
        idx = np.flatnonzero(shape_idx == s)
        block = rng.standard_normal((len(idx), m, n), np.float32)
        for k, i in enumerate(idx):
            mats[i] = block[k]
    return mats


def open_schedule(traffic: dict, seed: int, seconds: float) -> OpenSchedule:
    """``rate_per_s × seconds`` requests due in [0, seconds]: the gaps are
    the stratified quantiles of an exponential distribution (one fixed
    multiset for every seed) in an order the seed draws, scaled so the
    last request falls due as the window closes."""
    rate = float(traffic["rate_per_s"])
    shapes = mix_shapes(traffic["shapes"])
    total = max(1, int(round(rate * seconds)))
    rng = rng_for(seed, 2)
    u = (np.arange(total) + 0.5) / total
    gaps = rng.permutation(-np.log1p(-u) / rate)
    due = np.cumsum(gaps)
    due *= seconds / due[-1]
    counts = _spread(total, len(shapes))
    shape_idx = rng.permutation(np.repeat(np.arange(len(shapes)), counts))
    n_grad = int(round(total * float(traffic.get("grad_frac", 0.0))))
    grads = np.zeros(total, bool)
    grads[rng.permutation(total)[:n_grad]] = True
    mats = _matrices(shapes, shape_idx, rng)
    return OpenSchedule(due, mats, grads, shapes, shape_idx)


class ClosedStream:
    """Requests of a closed loop in the order they are sent: the k-th
    request's shape, matrix and gradient flag come from the seed.  Drawn
    in blocks of ``block`` requests, each block with the same count per
    shape and the same share of gradients."""

    def __init__(self, traffic: dict, seed: int, block: int = 4096):
        self.shapes = mix_shapes(traffic["shapes"])
        self.grad_frac = float(traffic.get("grad_frac", 0.0))
        self.seed = seed
        self.block = block
        self._blocks: dict[int, tuple] = {}

    def _block(self, b: int):
        if b not in self._blocks:
            rng = rng_for(self.seed, 3, b)
            counts = _spread(self.block, len(self.shapes))
            shape_idx = rng.permutation(
                np.repeat(np.arange(len(self.shapes)), counts))
            grads = np.zeros(self.block, bool)
            n_grad = int(round(self.block * self.grad_frac))
            grads[rng.permutation(self.block)[:n_grad]] = True
            self._blocks[b] = (_matrices(self.shapes, shape_idx, rng), grads,
                               shape_idx)
        return self._blocks[b]

    def prepare(self, count: int) -> None:
        """Draw the blocks that the first ``count`` requests need."""
        for b in range(-(-count // self.block)):
            self._block(b)

    def take(self, start: int, count: int):
        """Requests ``start .. start+count-1``: (matrices, grad flags)."""
        mats, grads = [], []
        for k in range(start, start + count):
            bm, bg, _ = self._block(k // self.block)
            mats.append(bm[k % self.block])
            grads.append(bool(bg[k % self.block]))
        return mats, grads
