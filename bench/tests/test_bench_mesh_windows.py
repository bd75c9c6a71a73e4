"""The four-chip cell past int32 (``paper_12x42_x4``) on the CPU: its
new reader on synthetic runs, a small run of its configuration on four
virtual CPU devices with the window limit patched so that each device's
grain runs in rank windows, and that run with one device's window
partial left out, which must come out not correct."""

import json
import os
import subprocess
import sys
import types

import pytest

from bench import run as harness
from bench.tests.conftest import REPO, make_root

CELL = "paper_12x42_x4"


def _synthetic(grid=(0, 2 * 16384), vals=2):
    c0, c1 = ({"plan_cache": {"grid_ranks": g}} if g is not None
              else {"plan_cache": {}} for g in grid)
    return types.SimpleNamespace(rec={"vals": [1.0] * vals},
                                 config={"shape": [5, 12]},
                                 counters0=c0, counters1=c1)


def test_grid_fill_reader():
    read = harness.metric_reader("grid_fill_pct.solve")
    # C(12, 5) = 792 ranks a solve in 16,384 slots
    assert read(_synthetic()) == pytest.approx(100.0 * 792 / 16384)
    assert read(_synthetic(grid=(5, 5 + 792))) == pytest.approx(200.0)
    # nothing to read: no counter (a program before it), no windowed
    # grid in the window, no solve
    assert read(_synthetic(grid=(None, None))) is None
    assert read(_synthetic(grid=(7, 7))) is None
    assert read(_synthetic(vals=0)) is None


_RUN = """
import json, sys
sys.path[:0] = [{repo!r}, {src!r}]
import jax
import jax.numpy as jnp
from repro.core import engine
engine.WINDOW_RANKS = 100     # (5, 12): 4 grains of 198 ranks, 2 windows each
if {broken!r}:
    from repro.kernels import ops
    real = ops.radic_windowed_partial_pallas

    def one_left_out(*a, **kw):   # device 1's first window dropped
        parts = real(*a, **kw)
        drop = ((jax.lax.axis_index("workers") == 1)
                & (jnp.arange(parts.shape[0]) == 0))
        return jnp.where(drop, 0.0, parts)
    ops.radic_windowed_partial_pallas = one_left_out
from bench import run as harness
sys.exit(harness.run(["--workload", {cell!r}, "--seed", "2147483659",
                      "--seconds", "1", "--trace", {trace!r}],
                     root={root!r}, devices_fn=lambda c: jax.devices()[:c]))
"""


def _run(tmp_path, broken: bool, trace: int) -> dict:
    root = make_root(str(tmp_path / "root"))
    code = _RUN.format(repo=REPO, src=os.path.join(REPO, "src"), root=root,
                       cell=CELL, broken=broken, trace=str(trace))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    return line


def test_small_mesh_windowed_run_is_correct(tmp_path):
    """Traced: correct, D · W_d = 8 windows a solve, and the grid's 4 · 2
    windows of one 8 · 256-rank step each hold 792 real ranks."""
    line = _run(tmp_path, broken=False, trace=1)
    assert line["correct"] is True and line["attempted"] > 0
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["windows_per_solve.solve"] == 8.0
    assert 0 < metrics["grid_fill_pct.solve"] <= 100
    assert metrics["grid_fill_pct.solve"] == pytest.approx(
        100.0 * 792 / (8 * 8 * 256))


def test_a_device_window_left_out_is_not_correct(tmp_path):
    line = _run(tmp_path, broken=True, trace=0)
    assert line["correct"] is False
    assert line["check"]["value_err"]["value"] > \
        line["check"]["value_err"]["limit"]
