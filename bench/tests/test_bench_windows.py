"""The cell past int32 (``paper_12x38``) on the CPU: its readers on
synthetic runs, a small run of its configuration with the window limit
patched so that the windowed kernel does the work, and that run with one
window's partial left out, which must come out not correct."""

import json
import types

import jax
import jax.numpy as jnp
import pytest

from bench import run as harness

CELL = "paper_12x38"


@pytest.fixture
def windowed(monkeypatch):
    """Rank windows of at most 100 ranks, so the small shape (5, 12),
    792 ranks, runs in 8 of them; a default engine of the test's own."""
    from repro.core import DetEngine, engine, set_default_engine
    monkeypatch.setattr(engine, "WINDOW_RANKS", 100)
    set_default_engine(DetEngine())
    yield
    set_default_engine(None)


def _run(root, capsys, trace=0, seed=2 ** 31 + 11):
    rc = harness.run(["--workload", CELL, "--seed", str(seed), "--seconds",
                      "1", "--trace", str(trace)], root=root,
                     devices_fn=lambda c: jax.devices()[:c])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _synthetic(op_s=None, windows=(0, 4), vals=2):
    dev = {"busy_s": 1.0, "op_s": op_s or {}}
    c0, c1 = ({"plan_cache": {"windows": w}} if w is not None
              else {"plan_cache": {}} for w in windows)
    return types.SimpleNamespace(
        rec={"vals": [1.0] * vals}, config={"shape": [12, 38]},
        device_kind="TPU v5 lite", devices=[dev] if op_s is not None
        else None, window_s=2.0, counters0=c0, counters1=c1)


def test_window_roofline_reader():
    read = harness.metric_reader("radic_window_roofline.solve")
    run = _synthetic({"radic_windowed_partial_pallas": 16.0,
                      "radic_batched_partial_pallas": 1.0})
    from bench import work
    t_min, _ = work.roofline_seconds(work.det_flops(12, 38, 2),
                                     work.det_bytes(12, 38, 2),
                                     "TPU v5 lite")
    assert read(run) == pytest.approx(100.0 * t_min / 16.0)
    # nothing to read: no windowed op (a program without windows), no
    # trace, no solve
    assert read(_synthetic({"radic_batched_partial_pallas": 1.0})) is None
    assert read(_synthetic(None)) is None
    assert read(_synthetic({"radic_windowed_partial_pallas": 1.0},
                           vals=0)) is None


def test_windows_per_solve_reader():
    read = harness.metric_reader("windows_per_solve.solve")
    assert read(_synthetic(windows=(6, 10))) == 2.0
    assert read(_synthetic(windows=(3, 3))) == 0.0   # another route
    assert read(_synthetic(windows=(None, None))) is None  # no counter
    assert read(_synthetic(vals=0)) is None


def test_small_windowed_run_is_correct(small_root, capsys, windowed):
    line = _run(small_root, capsys, trace=1)
    assert line["correct"] is True and line["attempted"] > 0
    assert line["metrics"]["windows_per_solve.solve"]["value"] == 8.0
    line = _run(small_root, capsys)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"solve_s", "setup_s"}


def test_a_window_left_out_is_not_correct(small_root, capsys, windowed,
                                          monkeypatch):
    from repro.kernels import ops
    real = ops.radic_windowed_partial_pallas

    def one_left_out(*a, **kw):     # window 3's partial dropped
        parts = real(*a, **kw)
        return jnp.where(jnp.arange(parts.shape[0]) == 3, 0.0, parts)
    monkeypatch.setattr(ops, "radic_windowed_partial_pallas", one_left_out)
    line = _run(small_root, capsys)
    assert line["correct"] is False
    assert line["check"]["value_err"]["value"] > \
        line["check"]["value_err"]["limit"]
