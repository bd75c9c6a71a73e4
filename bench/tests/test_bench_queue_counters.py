"""The readers of DetQueue's per-stage counters and gc hook, on
synthetic counters and in whole CPU runs; and the four-chip cell as
BENCHMARK.json has it, on four virtual CPU devices."""

import json
import os
import subprocess
import sys
import types

import jax
import pytest

from bench import run as harness
from bench.run import metric_reader
from bench.tests.conftest import REPO, make_root

SEGMENTS = {"queued_ms.latency": "queued_s",
            "staging_ms.latency": "staging_s",
            "inflight_ms.latency": "inflight_s",
            "delivery_ms.latency": "delivery_s"}
GC = ("gc_pause_pct.latency", "gc_pause_pct.throughput")


def _bucket(count, queued, staging, inflight, delivery):
    return {"count": count, "batches": 1, "ranks": 0,
            "wait_s": queued + staging + inflight + delivery,
            "queued_s": queued, "staging_s": staging,
            "inflight_s": inflight, "delivery_s": delivery}


def _run(counters0, counters1, t0=10.0, t1=12.0):
    return types.SimpleNamespace(counters0=counters0, counters1=counters1,
                                 rec={"t0": t0, "t1": t1})


def _counters(completed, buckets, pause_s=None):
    c = {"completed": completed, "buckets": buckets}
    if pause_s is not None:
        c["gc"] = {"collections": 0, "pause_s": pause_s}
    return c


C0 = _counters(4, {(2, 5): _bucket(4, 0.4, 0.2, 0.1, 0.02)}, pause_s=1.0)
C1 = _counters(14, {(2, 5): _bucket(8, 1.2, 0.6, 0.3, 0.06),
                    (3, 7): _bucket(6, 0.6, 0.3, 0.3, 0.03)}, pause_s=1.05)


def test_segments_read_the_window_and_add_up_to_the_sojourn():
    run = _run(C0, C1)
    got = {name: metric_reader(name)(run) for name in SEGMENTS}
    # Δ Σ field over Δ completed = 10 requests
    assert got["queued_ms.latency"] == pytest.approx(1e3 * 1.4 / 10)
    assert got["staging_ms.latency"] == pytest.approx(1e3 * 0.7 / 10)
    assert got["inflight_ms.latency"] == pytest.approx(1e3 * 0.5 / 10)
    assert got["delivery_ms.latency"] == pytest.approx(1e3 * 0.07 / 10)
    sojourn = metric_reader("queue_sojourn_ms.latency")(run)
    assert sum(got.values()) == pytest.approx(sojourn)


@pytest.mark.parametrize("name", GC)
def test_gc_pause_share_of_the_window(name):
    assert metric_reader(name)(_run(C0, C1)) == pytest.approx(
        100 * 0.05 / 2.0)


def _without(counters, *fields):
    return {"completed": counters["completed"],
            "buckets": {k: {f: v for f, v in b.items() if f not in fields}
                        for k, b in counters["buckets"].items()}}


@pytest.mark.parametrize("name", sorted(SEGMENTS) + list(GC))
@pytest.mark.parametrize("case", ["older_program", "solve_system",
                                  "nothing_completed"])
def test_nothing_to_read_is_none(name, case):
    if case == "older_program":    # wait_s alone, no gc hook
        run = _run(_without(C0, *SEGMENTS.values()),
                   _without(C1, *SEGMENTS.values()))
    elif case == "solve_system":   # radic_det counters: the plan cache
        run = _run({"plan_cache": {"misses": 1}},
                   {"plan_cache": {"misses": 1}})
    else:
        run = _run(_counters(4, {}, pause_s=1.0), _counters(4, {}, pause_s=1.0),
                   t0=5.0, t1=5.0)
    assert metric_reader(name)(run) is None


def test_traced_head_run_reports_the_segments(small_root, capsys):
    """A whole traced run of the head cell on the CPU: the four segments
    and the gc share are in the line, and the segments add up to the
    queue's sojourn."""
    rc = harness.run(["--workload", "serve_head_poisson", "--seed", "77",
                      "--seconds", "1", "--trace", "1"], root=small_root,
                     devices_fn=lambda c: jax.devices()[:c])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(SEGMENTS) | {"gc_pause_pct.latency"} <= set(metrics)
    assert sum(metrics[k] for k in SEGMENTS) == pytest.approx(
        metrics["queue_sojourn_ms.latency"], rel=1e-6)
    assert all(metrics[k] >= 0 for k in SEGMENTS)


_X4 = """
import sys
sys.path[:0] = [{repo!r}, {src!r}]
import jax
from bench import run as harness
spec = harness.load_cell("paper_12x32_x4", {root!r})
assert spec["cell"]["chips"] == 4 and spec["config"]["system"] == "radic_det_mesh"
sys.exit(harness.run(["--workload", "paper_12x32_x4", "--seed", "2147483659",
                      "--seconds", "1", "--trace", "0"], root={root!r},
                     devices_fn=lambda c: jax.devices()[:c]))
"""


def test_four_chip_cell_runs_correct(tmp_path):
    """paper_12x32_x4 as BENCHMARK.json has it, at a small shape."""
    root = make_root(str(tmp_path / "root"))
    spec = harness.load_cell("paper_12x32_x4", REPO)
    assert [m["name"] for m in spec["end_to_end"]] == ["solve_s", "setup_s"]
    assert {m["name"] for m in spec["per_layer"]} == {
        "radic_combo_roofline.solve", "idle_pct.solve"}
    code = _X4.format(repo=REPO, src=os.path.join(REPO, "src"), root=root)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is True
    assert set(line["metrics"]) == {"solve_s", "setup_s"}
