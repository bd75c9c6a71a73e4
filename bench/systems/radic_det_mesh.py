"""One Radic determinant at a time over a mesh of every chip of the
cell: ``repro.core.radic_det_distributed`` — the rank space cut into one
contiguous grain per device, the kernel per device, and a ``psum``."""

from __future__ import annotations

import numpy as np


class System:
    def __init__(self, config: dict, devices):
        from jax.sharding import Mesh
        from repro.core import radic_det_distributed
        self._det = radic_det_distributed
        self.backend = config["backend"]
        self.dtype = np.dtype(config["dtype"])
        self.mesh = Mesh(np.array(devices), ("workers",))

    def solve(self, A: np.ndarray) -> float:
        """The determinant of ``A``, on the host: a blocking call."""
        return float(self._det(A.astype(self.dtype), mesh=self.mesh,
                               backend=self.backend))

    def counters(self) -> dict:
        from repro.core import default_engine
        return {"plan_cache": default_engine().cache_info()}

    def close(self) -> None:
        pass


def make(config: dict, devices) -> System:
    return System(config, devices)
