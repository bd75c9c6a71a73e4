"""The harness end to end on the CPU: BENCHMARK.json keeps the
contract's shape, every cell's parts are found by name, a run's last
line has the result schema, and without an accelerator the command
prints no result and exits non-zero."""

import json
import os
import re
import subprocess
import sys

import jax
import pytest

from bench import run as harness
from bench.tests.conftest import REPO, add_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_the_contract_shape():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"] for c in b["configs"]}
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(cells) // 2)
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == \
        len(cells)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in cells
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", list(cells)):
            assert w in moved.get("workloads", list(cells))
    for w in cells:   # every cell: set-up, one more end-to-end, one layer
        spec = harness.load_cell(w, REPO)
        assert "setup_s" in [m["name"] for m in spec["end_to_end"]]
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]


def test_every_part_is_found_by_name():
    b = _bench()
    for c in b["configs"]:
        path = os.path.join(REPO, c["file"])
        assert path.startswith(os.path.join(REPO, "bench") + os.sep)
        with open(path) as f:
            cfg = json.load(f)
        assert hasattr(harness.system_module(cfg["system"]), "make")
        assert "limits" in cfg
    for w in b["workloads"]:
        spec = harness.load_cell(w["name"], REPO)
        assert spec["traffic"]["loop"] in ("solve", "open", "closed")
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    with pytest.raises(KeyError):
        harness.load_cell("no_such_cell", REPO)


def test_a_new_cell_is_data_only(small_root):
    """A later PR adds a cell by a new traffic file and an entry."""
    add_cell(small_root, {"name": "paper_again", "config": "paper_det",
                          "traffic": "solve_twice", "chips": 1,
                          "why": "a test"},
             mix={"loop": "solve", "why": "solves again"})
    spec = harness.load_cell("paper_again", small_root)
    assert spec["traffic"]["why"] == "solves again"
    # metrics without a workloads key apply to the new cell too
    assert "setup_s" in [m["name"] for m in spec["end_to_end"]]


def _cpu(chips):
    return jax.devices()[:chips]


def _last_line(capsys):
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("cell,trace", [("paper_12x32", 0),
                                        ("paper_12x32", 1),
                                        ("serve_head_poisson", 0),
                                        ("serve_hot_closed", 1)])
def test_last_line_schema(small_root, capsys, cell, trace):
    rc = harness.run(["--workload", cell, "--seed", str(2 ** 31 + 7),
                      "--seconds", "1", "--trace", str(trace)],
                     root=small_root, devices_fn=_cpu)
    assert rc == 0
    line, err = _last_line(capsys)
    assert list(line)[-1] == "check"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= \
        set(line)
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    if trace:
        assert "window_s" in line["device"] and "breakdown" in line
    else:
        assert set(line["metrics"]) >= {"setup_s"}
        assert len(line["metrics"]) == 2
    for name, row in line["check"].items():
        assert row["value"] <= row["limit"]
        assert f"check {name} " in err
    assert err.strip().splitlines()[-1].startswith("check ")


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench", "run.py"),
         "--workload", "paper_12x32", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr
