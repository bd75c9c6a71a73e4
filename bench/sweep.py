"""Sweep of the open-loop rate of a serving cell, in one process: the
knee is the highest rate at which the backlog does not grow over a
window.  Used once to fix a cell's ``rate_per_s``; not part of a run.

    python bench/sweep.py --workload serve_head_poisson --seed 7 \
        --seconds 10 --rates 2000,4000,8000

Prints one JSON line per rate: sojourn p50 and p99 of the window's first
and second half, the generator's lag, and how long the queue took to
drain after the window closed.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as harness  # bench/run.py; puts the checkout on sys.path

from bench import loops, traffic as gen  # noqa: E402


class _NoWindow:
    def open(self):
        pass

    def close(self):
        pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = harness.load_cell(args.workload)
    try:
        devices = harness.accelerator(int(spec["cell"]["chips"]))
    except harness.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    harness._compile_cache()
    config, mix = spec["config"], dict(spec["traffic"])
    system = harness.system_module(config["system"]).make(config, devices)
    try:
        shapes = gen.mix_shapes(mix["shapes"])
        system.warm(shapes, harness._grad_classes(mix),
                    gen.rng_for(args.seed, 9))
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix["rate_per_s"] = rate
            sched = gen.open_schedule(mix, args.seed + k, args.seconds)
            rec = loops.open_loop(system, sched, args.seconds, _NoWindow())
            soj = (rec["done"] - rec["due"]) * 1e3
            half = sched.due < args.seconds / 2
            row = {"rate_per_s": rate, "requests": len(sched),
                   "failed": rec["failed"]}
            for name, sel in (("first", half), ("second", ~half)):
                row[f"p50_ms_{name}"] = float(np.nanpercentile(soj[sel], 50))
                row[f"p99_ms_{name}"] = float(np.nanpercentile(soj[sel], 99))
            row["gen_lag_p99_ms"] = float(
                np.percentile((rec["sent"] - rec["due"]) * 1e3, 99))
            row["drain_s"] = float(np.nanmax(rec["done"]) - rec["t1"])
            print(json.dumps(row), flush=True)
    finally:
        system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
