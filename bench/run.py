"""Chip benchmark of the Radic determinant service: one run of one cell.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (``bench/configs/<config>.json``), its
traffic mix (``bench/traffic/<traffic>.json``) and its metrics
(``bench/metrics/<metric>.py``) are found by the names in
``BENCHMARK.json``.  A run checks that the accelerator is there, builds
the system the configuration names (``bench/systems/<system>.py``),
draws its requests from ``--seed``, warms every program the window uses,
measures for ``--seconds``, then compares what the window produced with
the plain reference (``bench/check.py``).  With ``--trace 1`` the window
runs under the profiler and the per-layer metrics are reported instead
of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``), and last ``check``, each number compared beside its
limit; the same numbers end standard error.  Without an accelerator, or
with fewer chips than the cell asks for, it prints no result and exits 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import check, loops, traffic as gen  # noqa: E402


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic mix and the
    metrics it reports, all found by name under ``root``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)

    def applies(metric):
        return name in metric.get("workloads", [name])

    return {"cell": cell, "config": config, "traffic": mix,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def _load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} module for {name!r}: {path}")
    mod_name = f"bench.{kind}.{name.replace('.', '__')}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    return _load_module("metrics", name).read


def system_module(name: str):
    """``bench/systems/<name>.py``."""
    return _load_module("systems", name)


def accelerator(chips: int):
    """The devices of the cell; raises :class:`NoAccelerator` on a CPU
    or with fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoAccelerator(f"no accelerator: JAX found {len(devs)} CPU "
                            "device(s)")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def _compile_cache() -> None:
    """JAX's persistent compilation cache in ``<checkout>/.jax_cache``,
    a fixed path, unless ``JAX_COMPILATION_CACHE_DIR`` names another."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# The profiler's first moments stall the host; the window opens after.
SETTLE_S = 1.0


class Window:
    """Opens and closes the measured window; with a trace directory, the
    window runs under the profiler, inside a ``bench.window`` span."""

    def __init__(self, logdir: str | None):
        self.logdir = logdir
        self._span = None
        self.compiles = 0
        self._watch = False
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, secs: float, **kw) -> None:
        # a trace precedes every compile and every load from the cache
        if self._watch and event == "/jax/core/compile/jaxpr_trace_duration":
            self.compiles += 1

    def open(self) -> None:
        import jax
        if self.logdir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            time.sleep(SETTLE_S)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
        self._watch = True

    def close(self) -> None:
        import jax
        self._watch = False
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        try:
            peaks.append(int((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0)))
        except Exception:  # noqa: BLE001 — a backend without the stats
            peaks.append(0)
    return max(peaks, default=0)


def measure(spec: dict, seed: int, seconds: float, devices,
            logdir: str | None) -> types.SimpleNamespace:
    """Set-up, the window and the check of one run."""
    config, mix = spec["config"], spec["traffic"]
    system = system_module(config["system"]).make(config, devices)
    window = Window(logdir)
    loop = mix["loop"]
    try:
        if loop == "solve":
            stream = gen.SolveStream(config["shape"], seed)
            system.solve(gen.rng_for(seed, 9).standard_normal(
                config["shape"], np.float32))     # one warm-up solve
            counters0 = system.counters()
            setup_s = time.perf_counter() - T_START
            rec = loops.solve_loop(system, stream, seconds, window)
        elif loop == "open":
            sched = gen.open_schedule(mix, seed, seconds)
            system.warm(sched.shapes, _grad_classes(mix),
                        gen.rng_for(seed, 9))
            counters0 = system.counters()
            setup_s = time.perf_counter() - T_START
            rec = loops.open_loop(system, sched, seconds, window)
        elif loop == "closed":
            stream = gen.ClosedStream(mix, seed)
            system.warm(stream.shapes, _grad_classes(mix),
                        gen.rng_for(seed, 9))
            counters0 = system.counters()
            setup_s = time.perf_counter() - T_START
            rec = loops.closed_loop(system, stream, int(mix["in_flight"]),
                                    seconds, window)
        else:
            raise ValueError(f"unknown traffic loop {loop!r}")
        counters1 = system.counters()
    finally:
        system.close()
    memory = _memory_peak(devices)
    if loop == "solve":
        numbers = check.check_solves(rec)
        attempted = len(rec["vals"])
    elif loop == "open":
        numbers = check.check_requests(sched.mats, sched.grads,
                                       rec["results"], seed)
        attempted = len(sched)
    else:
        n = len(rec["results"])
        mats, grads = rec["stream"].take(0, n)
        numbers = check.check_requests(mats, grads, rec["results"], seed)
        attempted = n
    return types.SimpleNamespace(
        rec=rec, setup_s=setup_s, config=config, traffic=mix,
        counters0=counters0, counters1=counters1, numbers=numbers,
        attempted=attempted, memory=memory, compiles=window.compiles,
        devices=None, window_s=0.0)


def _grad_classes(mix: dict) -> list[bool]:
    frac = float(mix.get("grad_frac", 0.0))
    return [False] + ([True] if frac > 0 else [])


def _device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def run(argv=None, root: str = ROOT, devices_fn=accelerator) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload, root)
    cell = spec["cell"]
    try:
        devices = devices_fn(int(cell["chips"]))
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if devices[0].platform != "cpu":
        _compile_cache()
    logdir = None
    if args.trace:
        logdir = os.path.join(os.environ.get("TMPDIR") or ROOT,
                              f".bench_trace_{os.getpid()}")
        shutil.rmtree(logdir, ignore_errors=True)
    try:
        r = measure(spec, args.seed, args.seconds, devices, logdir)
        device = _device_info(devices)
        device["memory_peak_bytes"] = r.memory
        r.device_kind = device["kind"]
        breakdown = None
        if logdir:
            from bench import trace
            planes = trace.load(logdir)
            t0, t1 = trace.find_span(planes, "bench.window")
            r.window_s = (t1 - t0) * 1e-9
            r.devices = trace.reduce_window(planes, t0, t1)[:len(devices)]
            if r.devices:
                device["busy_s"] = float(np.mean([d["busy_s"]
                                                  for d in r.devices]))
            device["window_s"] = r.window_s
            breakdown = trace.breakdown(planes, r.devices, t0, t1)
    finally:
        if logdir:
            shutil.rmtree(logdir, ignore_errors=True)
    wanted = spec["per_layer"] if args.trace else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        value = metric_reader(m["name"])(r)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    correct, table = check.verdict(r.numbers, spec["config"]["limits"])
    if r.compiles:
        print(f"bench: {r.compiles} program(s) traced inside the window",
              file=sys.stderr)
    for name, row in table.items():
        print(f"check {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": correct, "attempted": r.attempted,
            "failed": int(r.rec["failed"]), "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["check"] = table
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
