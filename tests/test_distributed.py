"""Distributed Radic determinant: grains/flat modes, multi-device via a
subprocess with forced host platform device count (the only place tests
use >1 device)."""

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (comb, plan_grains, radic_det_distributed,
                        radic_det_oracle)


def test_plan_grains_partitions_exactly():
    for total in [1, 7, 56, 1000]:
        for g in [1, 3, 8]:
            starts, lengths = plan_grains(total, g)
            assert starts[0] == 0
            assert sum(lengths) == total
            assert all(l >= 0 for l in lengths)
            for s, l, s2 in zip(starts, lengths, starts[1:] + [total]):
                assert s + l == s2


@pytest.mark.parametrize("mode,kw", [
    ("grains", dict(grains_per_device=1)),
    ("grains", dict(grains_per_device=4)),
    ("flat", dict(chunk=16)),
    ("flat", dict(chunk=16, backend="pallas")),
])
def test_single_device_modes(mode, kw, rng):
    A = rng.normal(size=(3, 8)).astype(np.float32)
    got = float(radic_det_distributed(jnp.asarray(A), mode=mode, **kw))
    want = radic_det_oracle(A)
    assert abs(got - want) <= 2e-3 * max(1.0, abs(want))


def test_grains_survive_uneven_split(rng):
    """56 subsets over 5 grains -> uneven lengths; reduction must be exact."""
    A = rng.normal(size=(5, 8)).astype(np.float32)
    got = float(radic_det_distributed(jnp.asarray(A), grains_per_device=5))
    want = radic_det_oracle(A)
    assert abs(got - want) <= 2e-3 * max(1.0, abs(want))


MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import radic_det_distributed, radic_det_oracle
    assert len(jax.devices()) == 8
    rng = np.random.default_rng(3)
    A = rng.normal(size=(4, 10)).astype(np.float32)
    want = radic_det_oracle(A)
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    for kw in (dict(mode="grains", grains_per_device=2),
               dict(mode="grains", grains_per_device=1),
               dict(mode="flat", chunk=32),
               dict(mode="flat", chunk=32, backend="pallas")):
        got = float(radic_det_distributed(jnp.asarray(A), mesh=mesh, **kw))
        assert abs(got - want) <= 2e-3 * max(1.0, abs(want)), (kw, got, want)
    print("MULTIDEV_OK")
""")


def test_eight_device_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "MULTIDEV_OK" in out.stdout, (out.stdout, out.stderr[-2000:])


# ------------------------------------------- Pallas mesh past WINDOW_RANKS
# Each device's grain runs as its own rank windows.  The window limit is
# patched small so that the Pallas interpreter runs them in seconds:
# (name, devices, mesh shape, m, n, limit).  "padded": C(11, 5) = 462
# over 4 devices is grains of 115 and 116 ranks, which straddle the
# limit 115, so grains of one window are padded with an empty one.
MESH_WINDOW_CASES = [
    ("two_devices", 2, (2,), 5, 12, 100),
    ("four_devices", 4, (4,), 5, 12, 100),
    ("padded", 4, (4,), 5, 11, 115),
    ("eight_devices", 8, (8,), 4, 13, 60),
    ("eight_devices_2d", 8, (2, 4), 5, 12, 50),
]

MESH_WINDOWS_SCRIPT = textwrap.dedent("""
    import os, sys, json, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import (DetEngine, comb, radic_det_distributed,
                            radic_det_oracle, set_default_engine)
    from repro.core import engine
    from repro.core.distributed import plan_grain_windows
    from repro.kernels.ops import window_grid
    from bench import reference
    assert len(jax.devices()) == 8
    out = {}
    for name, D, shape, m, n, limit in json.loads(sys.argv[1]):
        engine.WINDOW_RANKS = limit
        eng = DetEngine()
        set_default_engine(eng)
        axes = ("workers",) if len(shape) == 1 else ("data", "model")
        mesh = Mesh(np.array(jax.devices()[:D]).reshape(shape), axes)
        rng = np.random.default_rng(D * 100 + n)
        As = [rng.standard_normal((m, n)).astype(np.float32)
              for _ in range(2)]
        trace = name == "four_devices"
        if trace:
            logdir = tempfile.mkdtemp()
            jax.profiler.start_trace(logdir)
        got = []
        for A in As:
            got.append(float(radic_det_distributed(
                jnp.asarray(A), mesh=mesh, backend="pallas")))
        if trace:
            jax.profiler.stop_trace()
        info = eng.cache_info()
        (plan,) = [eng.plan(k.m, k.n, batched=False, backend="pallas",
                            mesh=mesh, chunk=k.chunk)
                   for k in eng.cached_keys()]
        w = plan_grain_windows(m, n, D)
        row = {"got": got, "oracle": [float(radic_det_oracle(A))
                                      for A in As],
               "reference": [float(v) for v in reference.radic_dp(
                   np.stack(As).astype(np.float64))],
               "plan_windows": plan.windows, "windows": info["windows"],
               "grid_ranks": info["grid_ranks"],
               "lengths": list(w.lengths),
               "slots": window_grid(w.lengths)[1],
               "rank_rows": plan.rank_rows, "walk": plan.walk}
        if trace:
            from jax.profiler import ProfileData
            import glob
            (path,) = glob.glob(logdir + "/**/*.xplane.pb", recursive=True)
            events = [ev for p in ProfileData.from_file(path).planes
                      for line in p.lines for ev in line.events]
            row["build"] = [dict(ev.stats)["windows"] for ev in events
                            if ev.name == "det.plan.build"]
            row["spans"] = sorted(
                (dict(ev.stats)["grain"], dict(ev.stats)["windows"],
                 dict(ev.stats)["window_ranks"]) for ev in events
                if ev.name == "det.plan.windows")
        out[name] = row
    print("MESH_WINDOWS " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def mesh_windows():
    """One subprocess on 8 virtual CPU devices runs every case."""
    import json
    root = os.path.dirname(os.path.dirname(__file__))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(root, "src"), root]))
    out = subprocess.run(
        [sys.executable, "-c", MESH_WINDOWS_SCRIPT,
         json.dumps(MESH_WINDOW_CASES)],
        capture_output=True, text=True, env=env, cwd=root, timeout=600)
    lines = [x for x in out.stdout.splitlines()
             if x.startswith("MESH_WINDOWS ")]
    assert lines, (out.stdout[-2000:], out.stderr[-4000:])
    return json.loads(lines[-1].split(" ", 1)[1])


def _grain_lengths(total, D):
    return plan_grains(total, D)[1]


@pytest.mark.parametrize("case", MESH_WINDOW_CASES, ids=lambda c: c[0])
def test_mesh_windows_match_oracle(mesh_windows, case):
    """radic_det_distributed(backend="pallas") past the (patched) window
    limit equals the oracle on seeded matrices, on 2, 4 and 8 devices."""
    row = mesh_windows[case[0]]
    for got, want in zip(row["got"], row["oracle"]):
        assert abs(got - want) <= 2e-5 * max(1.0, abs(want)), (got, want)
    assert row["rank_rows"] == 8 and row["walk"] == "successor"


def test_mesh_windows_match_bench_reference(mesh_windows):
    """The same values against the benchmark's float64 subset DP, which
    shares no code with the program."""
    row = mesh_windows["four_devices"]
    for got, want in zip(row["got"], row["reference"]):
        assert abs(got - want) <= 2e-5 * max(1.0, abs(want)), (got, want)


@pytest.mark.parametrize("case", MESH_WINDOW_CASES, ids=lambda c: c[0])
def test_mesh_windows_counted_per_call(mesh_windows, case):
    """Each call adds D · W_d windows to cache_info()["windows"] and
    D · W_d · steps · 8 · tile rank slots to ["grid_ranks"]."""
    name, D, _, m, n, limit = case
    row = mesh_windows[name]
    W_d = max(-(-g // limit) for g in _grain_lengths(comb(n, m), D))
    assert row["plan_windows"] == D * W_d == len(row["lengths"])
    assert row["windows"] == 2 * D * W_d
    longest = max(row["lengths"])
    steps = max(1, -(-(-(-longest // 256) * 256) // (8 * 256)))
    assert row["slots"] == D * W_d * steps * 8 * 256
    assert row["grid_ranks"] == 2 * row["slots"]
    # the windows hold every rank once, padded ones none
    assert sum(row["lengths"]) == comb(n, m)


def test_mesh_windows_padded_grains(mesh_windows):
    """Grains of 115 and 116 ranks at the limit 115: the short grains run
    one window and an empty one, the long grains two."""
    row = mesh_windows["padded"]
    assert row["lengths"] == [115, 0, 58, 58, 115, 0, 58, 58]


def test_mesh_windows_plan_spans(mesh_windows):
    """det.plan.build carries D · W_d windows; each grain's
    det.plan.windows span carries its grain index beside its windows and
    the longest window's ranks (4 grains of 198 ranks, 2 windows of 99)."""
    row = mesh_windows["four_devices"]
    assert row["build"] == [8]
    assert [tuple(s) for s in row["spans"]] == [(g, 2, 99) for g in range(4)]


def test_mesh_windows_paper_12x42_plan():
    """The cell's (12, 42) on 4 devices, planned on the host alone: the
    grain windows tile [0, C(42, 12)) exactly, each at most WINDOW_RANKS,
    2 per grain, and each start's slacks are what unrank_py and rank_py
    say of that start, the starts past 2³² included."""
    from repro.core import engine, rank_py, unrank_py
    from repro.core.distributed import plan_grain_windows
    from repro.core.pascal import INT32_MAX
    m, n, D = 12, 42, 4
    total = comb(n, m)
    w = plan_grain_windows(m, n, D)
    assert len(w) == D * 2 == 8
    assert w.starts[0] == 0 and sum(w.lengths) == total
    for a, la, b in zip(w.starts, w.lengths, w.starts[1:]):
        assert a + la == b
    assert max(w.lengths) <= engine.WINDOW_RANKS < INT32_MAX
    grains = plan_grains(total, D)[0]
    assert list(w.starts[::2]) == grains
    assert grains[1:] == [2764529222, 5529058444, 8293587666]
    assert sum(s > 2 ** 32 for s in w.starts) >= 4
    for q0, slack in zip(w.starts, w.slacks):
        combo = unrank_py(q0, n, m)
        want = np.zeros(n, np.int64)
        for i, c in enumerate(combo):
            # the last rank that keeps combo[:i + 1], plus one
            last = combo[:i + 1] + tuple(range(n - m + i + 2, n + 1))
            want[c - 1] = min(rank_py(last, n, m) + 1 - q0, INT32_MAX)
        assert (slack == want).all(), q0
