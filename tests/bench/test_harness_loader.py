"""The harness finds every piece of a cell by name, so a new cell comes as
new files and new entries, with no edit to a file that is there."""

from __future__ import annotations

import hashlib
import json
import os

import pytest
from harness_fixtures import run_cell, tiny_root  # noqa: F401 (fixtures)

from benchmark import harness
from benchmark.runs import Run

#: A graph maker of a new kind: a ring with chords, no R-MAT.
RING_GRAPH = '''
import numpy as np


def edges(graph):
    n = int(graph["vertices"])
    u = np.arange(n, dtype=np.int32)
    ring = (u + 1) % n
    chord = (u * 5 + 1) % n
    return n, np.concatenate([u, u]), np.concatenate([ring, chord]).astype(np.int32)
'''

#: A driver with a new arrival process: an open loop of single searches
#: in on/off bursts, ``burst`` due at once every ``period_s`` seconds,
#: each timed from its due time.
BURST_DRIVER = '''
import time

import numpy as np

from benchmark import harness, traffic
from benchmark.reference import Reference
from benchmark.runs import Run, build_graph


def drive(ctx):
    from bfs_tpu.cache.layout import LayoutCache, load_or_build_relay
    from bfs_tpu.graph.csr import Graph
    from bfs_tpu.models.bfs import RelayEngine

    n, src, dst = build_graph(ctx)
    rg, _ = load_or_build_relay(Graph(n, src, dst), cache=LayoutCache())
    eng = RelayEngine(rg)
    roots = traffic.rng(ctx.seed, 9).integers(0, n, 16)
    eng.run(int(roots[0]))
    run = Run(kind="fixture_bursts")
    run.setup_s = time.perf_counter() - ctx.t_start
    burst, period = int(ctx.params["burst"]), float(ctx.params["period_s"])
    latency, got = [], []
    t0 = time.perf_counter()
    while True:
        due = t0 + (len(got) // burst) * period
        if due - t0 >= ctx.seconds:
            break
        time.sleep(max(0.0, due - time.perf_counter()))
        root = int(roots[len(got) % roots.size])
        got.append((root, eng.run(root).dist))
        latency.append(time.perf_counter() - due)
    run.window_s = time.perf_counter() - t0
    run.attempted = run.searches = len(got)
    run.facts["latency_s"] = latency
    run.device = harness.device_info(ctx.devices)
    want = Reference(n, src, dst).distances([r for r, _ in got])
    wrong = sum(int(np.count_nonzero(d != w)) for (_, d), w in zip(got, want))
    run.checks = {"wrong_distances": {"value": wrong, "limit": 0}}
    return run
'''

LATENCY_METRIC = '''
import numpy as np


def read(run):
    latency = run.facts.get("latency_s")
    return 1e3 * float(np.median(latency)) if latency else None
'''


def _write(root: str, rel: str, text: str) -> None:
    with open(os.path.join(root, rel), "w") as f:
        f.write(text)


def _add_entries(root: str, **entries) -> None:
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for section, items in entries.items():
        bench[section].extend(items)
    with open(path, "w") as f:
        json.dump(bench, f)


def test_finds_the_cells_of_the_benchmark():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        cell, config, traffic = harness.load_cell(bench, w["name"])
        assert config["name"] == cell["config"]
        assert callable(harness.load_plugin("drivers", traffic["driver"]).drive)
        assert callable(harness.load_plugin(
            "graphs", config["graph"]["generator"]).edges)


@pytest.mark.parametrize("look", ["cell", "metric", "peaks", "driver", "graph"])
def test_unknown_names_are_errors(look):
    bench = harness.load_benchmark()
    with pytest.raises(harness.BenchError):
        if look == "cell":
            harness.load_cell(bench, "no-such-cell")
        elif look == "metric":
            harness.load_reader("no_such_metric")
        elif look == "peaks":
            harness.load_peaks("TPU v0 imaginary")
        elif look == "driver":
            harness.load_plugin("drivers", "no_such_driver")
        else:
            harness.load_plugin("graphs", "no_such_generator")


def test_a_cell_added_as_new_files_is_found(tiny_root):
    """A configuration, a traffic mix and a metric added as new files, and
    a cell that names them added to BENCHMARK.json."""
    with open(os.path.join(tiny_root, "benchmark/configs/graph500-bfs-s20.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "fixture-graph"
    cfg["graph"]["graph_seed"] = 4
    _write(tiny_root, "benchmark/configs/fixture-graph.json", json.dumps(cfg))
    _write(tiny_root, "benchmark/traffic/fixture_pairs.json", json.dumps(
        {"driver": "closed_search", "keys": 8, "batch": 2,
         "structure_seed": 1, "check_trees": 4}))
    _write(tiny_root, "benchmark/metrics/fixture_requests.py",
           "def read(run):\n    return run.requests or None\n")
    _add_entries(
        tiny_root,
        configs=[{"name": "fixture-graph", "source": "https://example.org",
                  "file": "benchmark/configs/fixture-graph.json",
                  "reduced": [], "why": "fixture"}],
        workloads=[{"name": "fixture.pairs", "config": "fixture-graph",
                    "traffic": "fixture_pairs", "chips": 1, "why": "fixture"}],
        per_layer=[{"name": "fixture_requests", "unit": "count",
                    "better": "higher", "source": "host_clock",
                    "layer": "fixture", "moves": "teps",
                    "workloads": ["fixture.pairs"]}])

    bench = harness.load_benchmark(tiny_root)
    cell, config, traffic = harness.load_cell(bench, "fixture.pairs", tiny_root)
    assert config["graph"]["graph_seed"] == 4
    assert traffic["batch"] == 2
    entries = harness.cell_metrics(bench, "fixture.pairs", trace=True)
    assert [m["name"] for m in entries] == ["fixture_requests"]
    run = Run(kind="closed_search", requests=3)
    got = harness.read_metrics(entries, run, tiny_root)
    assert got == {"fixture_requests": {"value": 3.0, "unit": "count"}}
    # An end-to-end metric without a workloads list reaches the new cell
    # too; one with a list only the cells it names.
    e2e = [m["name"] for m in harness.cell_metrics(bench, "fixture.pairs", False)]
    assert e2e == ["setup_s"]


def _digests(root: str) -> dict:
    out = {}
    for folder, _, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            if name.endswith((".py", ".json")):
                path = os.path.join(folder, name)
                with open(path, "rb") as f:
                    out[path] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_a_new_driver_and_graph_maker_run_as_files(tiny_root, run_cell):
    """A cell whose graph, arrival process and loop are all new: a graph
    maker, a driver, a traffic mix, a metric and a configuration added as
    files, and entries added to BENCHMARK.json, run whole on the CPU with
    no file of the benchmark edited."""
    before = _digests(tiny_root)
    _write(tiny_root, "benchmark/graphs/fixture_ring.py", RING_GRAPH)
    _write(tiny_root, "benchmark/drivers/fixture_bursts.py", BURST_DRIVER)
    _write(tiny_root, "benchmark/metrics/fixture_latency_p50_ms.py", LATENCY_METRIC)
    _write(tiny_root, "benchmark/traffic/fixture_bursts.json", json.dumps(
        {"driver": "fixture_bursts", "burst": 4, "period_s": 0.2}))
    _write(tiny_root, "benchmark/configs/fixture-ring.json", json.dumps(
        {"name": "fixture-ring", "graph": {"generator": "fixture_ring",
                                           "vertices": 256}}))
    _add_entries(
        tiny_root,
        configs=[{"name": "fixture-ring", "source": "https://example.org",
                  "file": "benchmark/configs/fixture-ring.json",
                  "reduced": [], "why": "fixture"}],
        workloads=[{"name": "fixture.bursts", "config": "fixture-ring",
                    "traffic": "fixture_bursts", "chips": 1, "why": "fixture"}],
        end_to_end=[{"name": "fixture_latency_p50_ms", "unit": "ms",
                     "better": "lower", "bound": 0.1, "source": "host_clock",
                     "workloads": ["fixture.bursts"]}])

    res = run_cell("fixture.bursts", seconds=0.6)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 8
    assert set(res["metrics"]) == {"fixture_latency_p50_ms", "setup_s"}
    assert res["metrics"]["fixture_latency_p50_ms"]["value"] > 0
    after = _digests(tiny_root)
    assert {p: after[p] for p in before} == before


def test_a_reader_with_nothing_to_read_is_left_out():
    bench = harness.load_benchmark()
    entries = harness.cell_metrics(bench, "g500-s20.single", trace=True)
    run = Run(kind="closed_search", searches=10, requests=10)
    # No trace: the device metrics find nothing and are left out.
    assert harness.read_metrics(entries, run) == {}


@pytest.mark.parametrize("workload", ["g500-s20.single", "g500-s20.multi64"])
def test_each_cell_has_its_metrics(workload):
    bench = harness.load_benchmark()
    for trace in (False, True):
        for m in harness.cell_metrics(bench, workload, trace):
            assert callable(harness.load_reader(m["name"]))
