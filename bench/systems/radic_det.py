"""One Radic determinant at a time through ``repro.core.radic_det``:
``DetEngine`` → the fused kernel at B = 1 (the paper's workload)."""

from __future__ import annotations

import numpy as np


class System:
    def __init__(self, config: dict, devices):
        from repro.core import radic_det
        self._det = radic_det
        self.backend = config["backend"]
        self.dtype = np.dtype(config["dtype"])
        self.device = devices[0]

    def solve(self, A: np.ndarray) -> float:
        """The determinant of ``A``, on the host: a blocking call."""
        import jax
        return float(self._det(jax.device_put(A.astype(self.dtype),
                                              self.device),
                               backend=self.backend))

    def counters(self) -> dict:
        from repro.core import default_engine
        return {"plan_cache": default_engine().cache_info()}

    def close(self) -> None:
        pass


def make(config: dict, devices) -> System:
    return System(config, devices)
