"""The control of a cell's comparison: the reference put in the
program's place at bfloat16 operand precision (float32 arithmetic, on
the default device), compared with the float64 reference exactly as a
run compares the program.  Its readings set the upper end of each limit;
the program's readings over a dozen seeds or more set the lower end.

    python bench/control.py --workload paper_12x32 --seeds 1,2,3 --seconds 10

For a solve loop the control solves the first ``SOLVES`` matrices a run
with that seed solves; for a serving loop, the sample a run compares.

Prints one JSON line per seed with the numbers the check compares.  Not
part of a run.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as harness  # bench/run.py; puts the checkout on sys.path

from bench import check, reference, traffic as gen  # noqa: E402


SOLVES = 4


def readings(spec: dict, seed: int, seconds: float, xp):
    config, mix = spec["config"], spec["traffic"]
    if mix["loop"] == "solve":
        stream = gen.SolveStream(config["shape"], seed)
        As = np.stack([stream.next() for _ in range(SOLVES)])
        return {"value_err": check.value_gap(
            list(As), reference.control_values(As, xp))}
    if mix["loop"] == "open":
        sched = gen.open_schedule(mix, seed, seconds)
        mats, grads = sched.mats, sched.grads
    else:
        stream = gen.ClosedStream(mix, seed)
        n = int(mix["in_flight"]) * 64
        mats, grads = stream.take(0, n)
    value_idx, grad_idx = check.sample(grads, range(len(mats)), seed)
    results: dict = {}
    for idx, fn in ((value_idx, reference.control_values),
                    (grad_idx, reference.control_grads)):
        groups: dict = {}
        for i in idx:
            groups.setdefault(mats[i].shape, []).append(i)
        for g in groups.values():
            for i, r in zip(g, fn(np.stack([mats[i] for i in g]), xp)):
                results[i] = r
    return check.request_gaps(mats, results, value_idx, grad_idx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    spec = harness.load_cell(args.workload)
    import jax
    import jax.numpy as jnp
    print(f"control: device {jax.devices()[0].device_kind}",
          file=sys.stderr)
    for seed in (int(s) for s in args.seeds.split(",")):
        row = readings(spec, seed, args.seconds, jnp)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
