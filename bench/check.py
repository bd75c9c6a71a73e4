"""The comparison that decides ``correct``.

What the timed path produced, at the timed sizes, against the plain
reference (:mod:`bench.reference`), once the window has closed:

* ``value_err`` — the widest gap |served − reference| over the values
  compared, in units of the sum's term size SCALE = sqrt(det(A·Aᵀ));
* ``grad_err`` — the widest gap over the entries of the gradients
  compared, each in units of its own term size;
* ``missing`` — answers that failed or never came (limit 0).

Solve loops compare every solve.  Serving loops compare a sample drawn
from the seed of the requests the window sent.  Each number is printed
beside its limit; the limits are the configuration's (``limits``).
"""

from __future__ import annotations

import numpy as np

from . import reference
from .traffic import rng_for

__all__ = ["CHECK_VALUES", "CHECK_GRADS", "value_gap", "grad_gap",
           "check_solves", "sample", "request_gaps", "check_requests",
           "verdict"]

# How many answered value and gradient requests a serving run compares.
CHECK_VALUES = 4096
CHECK_GRADS = 64


def value_gap(mats, got) -> float:
    """max |got − ref| / SCALE over matrices of one shape."""
    As = np.stack(mats).astype(np.float64)
    ref = reference.radic_dp(As)
    scale = reference.radic_scale(As)
    return float(np.max(np.abs(np.asarray(got, np.float64) - ref) / scale))


def grad_gap(mats, got) -> float:
    """max over entries of |got − ref| / (the entry's term size)."""
    As = np.stack(mats).astype(np.float64)
    ref = reference.radic_grad_dp(As)
    scale = reference.grad_scale(As)
    G = np.stack([np.asarray(g, np.float64) for g in got])
    return float(np.max(np.abs(G - ref) / scale))


def check_solves(rec: dict) -> dict:
    """Every solve of the window against the reference."""
    return {"value_err": value_gap(rec["mats"], rec["vals"]),
            "missing": float(rec["failed"])}


def _by_shape(idx, mats):
    groups: dict = {}
    for i in idx:
        groups.setdefault(mats[i].shape, []).append(i)
    return groups.values()


def sample(grads, answered, seed: int):
    """The value and the gradient requests a run compares: up to
    ``CHECK_VALUES`` and ``CHECK_GRADS`` of the answered ones, drawn
    from the seed."""
    rng = rng_for(seed, 4)
    vals = [i for i in answered if not grads[i]]
    grs = [i for i in answered if grads[i]]
    nv = min(len(vals), CHECK_VALUES)
    ng = min(len(grs), CHECK_GRADS)
    pick_v = rng.choice(len(vals), nv, replace=False) if nv else []
    pick_g = rng.choice(len(grs), ng, replace=False) if ng else []
    return [vals[p] for p in pick_v], [grs[p] for p in pick_g]


def request_gaps(mats, results, value_idx, grad_idx) -> dict:
    """``value_err`` and ``grad_err`` of the sampled answers."""
    out = {}
    if len(value_idx):
        out["value_err"] = max(
            value_gap([mats[i] for i in g], [results[i] for i in g])
            for g in _by_shape(value_idx, mats))
    if len(grad_idx):
        out["grad_err"] = max(
            grad_gap([mats[i] for i in g], [results[i] for i in g])
            for g in _by_shape(grad_idx, mats))
    return out


def check_requests(mats, grads, results, seed: int) -> dict:
    """A sample, drawn from the seed, of the value and the gradient
    requests the window sent, against the reference."""
    answered = [i for i, r in enumerate(results) if r is not None]
    value_idx, grad_idx = sample(grads, answered, seed)
    out = {"missing": float(len(results) - len(answered))}
    out.update(request_gaps(mats, results, value_idx, grad_idx))
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number at or under
    its limit; a number without a limit, or a NaN, fails."""
    table, ok = {}, True
    for name, value in numbers.items():
        limit = float(limits.get(name, 0.0)) if name != "missing" else 0.0
        table[name] = {"value": value, "limit": limit}
        if not value <= limit:
            ok = False
    return ok, table
