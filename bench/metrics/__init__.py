"""Metric readers, one module per metric, found by the metric's name in
``BENCHMARK.json`` (``bench/metrics/<name>.py``).  Each has
``read(run) -> float | None``; ``None`` means there was nothing to read
in this run, and the metric is left out of the result line.

``run`` carries what a run measured: ``rec`` (the loop's record),
``setup_s``, ``config``, ``traffic``, ``device_kind``, ``counters0`` and
``counters1`` (the system's counters as the window opened and closed),
and with ``--trace 1`` ``devices`` (per-device reduction of the trace,
:func:`bench.trace.reduce_window`) and ``window_s``.  The helpers below
are shared by several readers.
"""

from __future__ import annotations

import re

import numpy as np

VALUE_KERNEL = re.compile(r"^radic_batched_partial_pallas$")


def traced(run) -> bool:
    return getattr(run, "devices", None) is not None and run.window_s > 0


def idle_pct(run):
    """100 × (1 − busy / window), busy averaged over the devices."""
    if not traced(run) or not run.devices:
        return None
    busy = float(np.mean([d["busy_s"] for d in run.devices]))
    return 100.0 * (1.0 - busy / run.window_s)


def kernel_seconds(run, pattern=VALUE_KERNEL) -> list[float]:
    """Seconds of the matching kernel on each device inside the window."""
    return [sum(s for name, s in d["op_s"].items() if pattern.match(name))
            for d in run.devices]


def delta(run, *path) -> float:
    """Change across the window of a counter of the system."""
    a, b = run.counters0, run.counters1
    for key in path:
        a, b = a[key], b[key]
    return float(b) - float(a)


def bucket_delta(run, field: str) -> float:
    """Change across the window of a per-bucket counter, summed."""
    b0 = run.counters0.get("buckets", {})
    b1 = run.counters1.get("buckets", {})
    return float(sum(v[field] - b0.get(k, {}).get(field, 0)
                     for k, v in b1.items()))
