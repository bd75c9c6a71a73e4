"""Pallas TPU kernels for the paper's compute hot-spot: the per-rank
unrank → gather → determinant pipeline, fused into the value, gradient
and rank-window kernels that serve (:mod:`.radic_fused`,
:mod:`.radic_windowed`) behind the plan-time guards of :mod:`.ops`.
They compile for the TPU and run in interpret mode on the CPU, where the
tests check them against the numpy oracles in :mod:`repro.kernels.ref`."""

from . import ops, ref
from .radic_fused import radic_partial_pallas

__all__ = ["ops", "ref", "radic_partial_pallas"]
