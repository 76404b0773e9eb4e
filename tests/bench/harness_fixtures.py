"""Fixtures of the benchmark's CPU tests, imported by the test files that
use them (a ``conftest.py`` here would shadow the one of ``tests/``): a
copy of the benchmark in a temporary checkout root, cut to a tiny graph,
so that a whole run can be driven on the CPU without the chip."""

from __future__ import annotations

import json
import os
import shutil
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class NullMeter:
    """Stands in for the compile meter, which hooks JAX's global logging."""

    def snapshot(self):
        return {"compile_s": 0.0, "compiles": 0, "cache_hits": 0,
                "cache_misses": 0, "missed": []}


def make_root(path: str, scale: int = 7) -> str:
    """A checkout root holding the benchmark, its graphs cut to
    ``scale`` and its traffic cut to match."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(path, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in bench["configs"]:
        cfg_path = os.path.join(path, entry["file"])
        with open(cfg_path) as f:
            cfg = json.load(f)
        cfg["graph"]["scale"] = scale
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
    for cell in bench["workloads"]:
        t_path = os.path.join(path, "benchmark", "traffic", cell["traffic"] + ".json")
        with open(t_path) as f:
            params = json.load(f)
        params["batch"] = min(params["batch"], 16)
        params["keys"] = 4 * params["batch"]
        params["check_trees"] = min(params["check_trees"], 8)
        with open(t_path, "w") as f:
            json.dump(params, f)
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return path


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A tiny checkout root; the program's artifact caches go to a
    temporary directory."""
    monkeypatch.setenv("BFS_TPU_CACHE_DIR", str(tmp_path / "cache"))
    return make_root(str(tmp_path / "root"))


@pytest.fixture
def run_cell(tiny_root):
    """``run_cell(workload, seed=..., seconds=..., **swap)``: one whole
    run of a cell of the tiny root on the CPU, as the chip would run it."""
    import jax

    from benchmark import run as bench_run

    def run(workload: str, seed: int = 12345678901, seconds: float = 0.5,
            trace: int = 0, **swap) -> dict:
        args = SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                               trace=trace)
        return bench_run.execute(args, root=tiny_root, devices=jax.devices()[:1],
                                 meter=NullMeter(), **swap)

    return run
