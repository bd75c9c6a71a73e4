"""Reduction of a profiler trace to device busy time, kernel time,
collective time and the breakdown of a traced window.

A trace is reduced from plain data, so that a test can hand it a small
synthetic one: a list of planes ``{"name", "lines": {line: [(event,
start_ns, duration_ns), ...]}}``.  :func:`load` builds that from the
``.xplane.pb`` file that ``jax.profiler`` writes.

Device planes are named ``/device:TPU:<i>``; their ``XLA Ops`` line holds
one event per HLO operation, named by its HLO text
(``%name.3 = f32[...] custom-call(...), custom_call_target=...``).  The
host plane ``/host:CPU`` holds the benchmark's own spans
(``jax.profiler.TraceAnnotation``) on the clock the device events use.
"""

from __future__ import annotations

import glob
import os
import re

__all__ = ["load", "device_planes", "find_span", "op_name", "is_collective",
           "union_seconds", "reduce_window", "breakdown"]

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
# the opcode of a collective, as HLO text prints it before its operands
_COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?\(")


def load(logdir: str) -> list[dict]:
    """The newest ``.xplane.pb`` under ``logdir`` as plain planes."""
    import jax
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def device_planes(planes: list[dict]) -> list[dict]:
    """Device planes in device order."""
    devs = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    return sorted(devs, key=lambda p: int(p["name"].rsplit(":", 1)[1]))


def find_span(planes: list[dict], name: str) -> tuple[float, float]:
    """(start_ns, end_ns) of the host span ``name``; the last if several."""
    found = None
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for events in p["lines"].values():
            for ev, start, dur in events:
                if ev == name and (found is None or start > found[0]):
                    found = (start, start + dur)
    if found is None:
        raise KeyError(f"host span {name!r} not in the trace")
    return found


def op_name(event: str) -> str:
    """``%radic_batched_partial_pallas.1 = f32[...] ...`` ->
    ``radic_batched_partial_pallas``: the HLO instruction's name without
    its numeric suffix."""
    head = event.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def is_collective(event: str) -> bool:
    """Whether the HLO operation is a collective, by its opcode: a
    ``psum`` is ``%psum.7 = f32[] all-reduce(...)``."""
    return bool(_COLLECTIVE.search(event.split(" = ", 1)[-1]))


def _clipped(events, t0: float, t1: float):
    for ev, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            yield ev, a, b


def union_seconds(intervals) -> float:
    """Length of the union of ``(start_ns, end_ns)`` intervals, seconds."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total * 1e-9


def reduce_window(planes: list[dict], t0: float, t1: float) -> list[dict]:
    """Per device, inside [t0, t1] (ns): busy seconds (union of its op
    intervals), seconds per op name, collective seconds, and the busy
    intervals themselves."""
    out = []
    for p in device_planes(planes):
        ops = list(_clipped(p["lines"].get(OPS_LINE, []), t0, t1))
        per_op: dict[str, float] = {}
        coll = 0.0
        for ev, a, b in ops:
            name = op_name(ev)
            per_op[name] = per_op.get(name, 0.0) + (b - a) * 1e-9
            if is_collective(ev):
                coll += (b - a) * 1e-9
        out.append({"device": p["name"],
                    "busy_s": union_seconds((a, b) for _, a, b in ops),
                    "op_s": per_op, "collective_s": coll,
                    "intervals": sorted((a, b) for _, a, b in ops)})
    return out


def _host_at(planes: list[dict], t: float) -> str:
    """The innermost host span that covers time ``t``, or ``idle``."""
    best = None
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for events in p["lines"].values():
            for ev, start, dur in events:
                if start <= t <= start + dur and (best is None
                                                  or dur < best[1]):
                    best = (ev, dur)
    return best[0] if best else "idle"


def breakdown(planes: list[dict], devices: list[dict], t0: float,
              t1: float, top: int = 10) -> dict:
    """The device ops that took most time (seconds, mean over devices)
    and the longest idle gaps of the first device, each named by the
    host span that covered its middle."""
    ndev = max(1, len(devices))
    totals: dict[str, float] = {}
    for d in devices:
        for name, s in d["op_s"].items():
            totals[name] = totals.get(name, 0.0) + s / ndev
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    if devices:
        prev = t0
        for a, b in devices[0]["intervals"] + [(t1, t1)]:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_host_at(planes, (a + b) / 2), (b - a) * 1e-9]
             for a, b in gaps[:top]]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}
