"""Fixtures of the benchmark's CPU tests: a small checkout root that
holds BENCHMARK.json with every cell, at shapes the Pallas interpreter
runs in seconds."""

from __future__ import annotations

import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SMALL_SHAPE = [5, 12]


def make_root(path: str) -> str:
    """A root with the real BENCHMARK.json and shrunken data files: every
    configuration under ``bench/configs``, every mix the cells use."""
    os.makedirs(os.path.join(path, "bench", "configs"))
    os.makedirs(os.path.join(path, "bench", "traffic"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), path)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    configs = os.path.join(REPO, "bench", "configs")
    for name in os.listdir(configs):
        with open(os.path.join(configs, name)) as f:
            cfg = json.load(f)
        if "shape" in cfg:
            cfg["shape"] = SMALL_SHAPE
        with open(os.path.join(path, "bench", "configs", name), "w") as f:
            json.dump(cfg, f)
    small = {"open": {"rate_per_s": 150, "shapes": [[3, 7], [4, 9]]},
             "closed": {"in_flight": 16, "shapes": [[4, 9]]}}
    for w in bench["workloads"]:
        name = w["traffic"]
        with open(os.path.join(REPO, "bench", "traffic",
                               name + ".json")) as f:
            mix = json.load(f)
        mix.update(small.get(mix["loop"], {}))
        with open(os.path.join(path, "bench", "traffic", name + ".json"),
                  "w") as f:
            json.dump(mix, f)
    return path


@pytest.fixture
def small_root(tmp_path, monkeypatch):
    """A small root, and a serving run's check cut to a sample that the
    CPU compares in seconds."""
    from bench import check
    monkeypatch.setattr(check, "CHECK_VALUES", 64)
    monkeypatch.setattr(check, "CHECK_GRADS", 8)
    return make_root(str(tmp_path / "root"))


def add_cell(root: str, cell: dict, config: dict | None = None,
             mix: dict | None = None) -> None:
    """Add a cell to the root's BENCHMARK.json, with its configuration
    entry and its traffic file where given: data only, as a later change
    adds one."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append(cell)
    if config is not None:
        bench["configs"].append(config)
    with open(path, "w") as f:
        json.dump(bench, f)
    if mix is not None:
        with open(os.path.join(root, "bench", "traffic",
                               cell["traffic"] + ".json"), "w") as f:
            json.dump(mix, f)
