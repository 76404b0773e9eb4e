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


def make_root(path: str, scale: int = 7, source: str = REPO) -> str:
    """A checkout root holding the benchmark of ``source``, cut to a tiny
    size.  Only what a cell's files declare is cut: a configuration's
    ``graph.scale`` to ``scale``; a traffic mix's ``batch`` to at most 16,
    its ``keys`` to four batches and its ``check_trees`` to at most 8;
    then whatever its driver's optional ``tiny(params) -> params`` cuts."""
    from benchmark import harness

    shutil.copytree(os.path.join(source, "benchmark"),
                    os.path.join(path, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(source, "BENCHMARK.json"), path)
    bench = harness.load_benchmark(path)
    for entry in bench["configs"]:
        cfg_path = os.path.join(path, entry["file"])
        with open(cfg_path) as f:
            cfg = json.load(f)
        if "scale" in cfg.get("graph", {}):
            cfg["graph"]["scale"] = scale
            with open(cfg_path, "w") as f:
                json.dump(cfg, f)
    for name in sorted({cell["traffic"] for cell in bench["workloads"]}):
        t_path = os.path.join(path, "benchmark", "traffic", name + ".json")
        with open(t_path) as f:
            params = json.load(f)
        if "batch" in params:
            params["batch"] = min(params["batch"], 16)
        if "keys" in params:
            params["keys"] = 4 * params.get("batch", 1)
        if "check_trees" in params:
            params["check_trees"] = min(params["check_trees"], 8)
        tiny = getattr(harness.load_plugin("drivers", params["driver"], path),
                       "tiny", None)
        if tiny is not None:
            params = tiny(params)
        with open(t_path, "w") as f:
            json.dump(params, f)
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
