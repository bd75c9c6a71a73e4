"""A run with the timed path broken underneath must come out not
correct: an answer altered where it is produced, half of a batch left
out, and the exchange between chips left out.  The harness's look for
a chip is skipped (the CPU stands in); the rest of the run is whole."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import check, run as harness
from bench.tests.conftest import REPO, add_cell, make_root


def _run(root, cell, capsys, seed=11):
    rc = harness.run(["--workload", cell, "--seed", str(seed), "--seconds",
                      "1", "--trace", "0"], root=root,
                     devices_fn=lambda c: jax.devices()[:c])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_runs_are_correct(small_root, capsys):
    for cell in ("paper_12x32", "serve_head_poisson"):
        assert _run(small_root, cell, capsys)["correct"] is True


def test_answer_altered_where_produced(small_root, capsys, monkeypatch):
    from repro.kernels import ops
    real = ops.radic_partial_pallas

    def altered(A, *a, **kw):     # the kernel's answer, sign flipped
        return -real(A, *a, **kw)
    monkeypatch.setattr(ops, "radic_partial_pallas", altered)
    line = _run(small_root, "paper_12x32", capsys)
    assert line["correct"] is False
    assert line["check"]["value_err"]["value"] > \
        line["check"]["value_err"]["limit"]


@pytest.mark.parametrize("which", ["values", "gradients"])
def test_half_of_the_batch_left_out(small_root, capsys, monkeypatch, which):
    from repro.kernels import ops
    name = ("radic_det_batched_pallas" if which == "values"
            else "radic_det_batched_grad_pallas")
    real = getattr(ops, name)

    def half(As, *a, **kw):        # every other matrix of the batch dropped
        out = real(As, *a, **kw)
        keep = jnp.arange(out.shape[0]) % 2 == 0
        return jnp.where(keep.reshape((-1,) + (1,) * (out.ndim - 1)),
                         out, 0)
    monkeypatch.setattr(ops, name, half)
    monkeypatch.setattr(check, "CHECK_GRADS", 32)
    # a closed loop keeps batches full whatever the timing
    add_cell(small_root, {"name": "closed_mixed", "config": "det_serve",
                          "traffic": "closed_mixed", "chips": 1,
                          "why": "a test"},
             mix={"loop": "closed", "in_flight": 16, "shapes": [[4, 9]],
                  "grad_frac": 0.5})
    line = _run(small_root, "closed_mixed", capsys)
    assert line["correct"] is False, line
    key = "value_err" if which == "values" else "grad_err"
    assert line["check"][key]["value"] > line["check"][key]["limit"]


_NO_PSUM = """
import json, sys
sys.path[:0] = [{repo!r}, {src!r}]
import jax
import repro.core.distributed as dist
dist.psum_scalar = lambda x, axes: x      # the exchange left out
from bench import run as harness
sys.exit(harness.run(["--workload", "paper_12x32_x4", "--seed", "5",
                      "--seconds", "1", "--trace", "0"], root={root!r},
                     devices_fn=lambda c: jax.devices()[:c]))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_exchange_between_chips_left_out(tmp_path, broken):
    """The four-chip cell (bench/configs/paper_det_x4.json, not yet in
    BENCHMARK.json), added as data, on four virtual CPU devices."""
    root = make_root(str(tmp_path / "root"))
    add_cell(root, {"name": "paper_12x32_x4", "config": "paper_det_x4",
                    "traffic": "solve_loop", "chips": 4, "why": "a test"},
             config={"name": "paper_det_x4", "source": "a test",
                     "file": "bench/configs/paper_det_x4.json",
                     "reduced": [], "why": "a test"})
    code = _NO_PSUM.format(repo=REPO, src=os.path.join(REPO, "src"),
                           root=root)
    if not broken:
        code = code.replace("dist.psum_scalar = ", "_ = ")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is (not broken)
