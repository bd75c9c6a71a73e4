"""An in-process ``DetQueue``, built as ``det_serve --backend pallas``
builds it, with the configuration's bucket policy."""

from __future__ import annotations


class System:
    def __init__(self, config: dict, devices):
        from repro.launch.det_queue import BucketPolicy, DetQueue
        policy = BucketPolicy(**config["policy"])
        self.queue = DetQueue(chunk=config.get("chunk", 2048),
                              backend=config["backend"], policy=policy,
                              dtype=config["dtype"],
                              max_pending=config.get("max_pending"))

    def submit_many(self, mats, grads):
        """Futures of ``mats``; ``grads`` are the gradient flags."""
        return self.queue.submit_many(
            mats, [(bool(g), 1.0) for g in grads])

    def warm(self, shapes, grad_classes, rng) -> None:
        """Serve one full batch of every (shape, value|gradient) bucket,
        so that every program the window uses is compiled or loaded."""
        import numpy as np
        cap = self.queue.policy.max_batch
        for m, n in shapes:
            mats = [rng.standard_normal((m, n), np.float32)
                    for _ in range(cap)]
            for g in grad_classes:
                for f in self.submit_many(mats, [g] * cap):
                    f.result(timeout=600)

    def counters(self) -> dict:
        return self.queue.snapshot()

    def close(self) -> None:
        self.queue.close()


def make(config: dict, devices) -> System:
    return System(config, devices)
