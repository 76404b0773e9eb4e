"""The harness finds every piece of a cell by name, so a new cell comes as
new files and new entries, with no edit to a file that is there."""

from __future__ import annotations

import hashlib
import json
import os
import shutil

import pytest
from harness_fixtures import REPO, make_root, run_cell, tiny_root  # noqa: F401 (fixtures)

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

#: A second kernel's driver, of a kind other than ``closed_search``: a
#: closed loop through the shared window, with the plain reference in the
#: engine's place; its control stops each search one level short.
PLAIN_DRIVER = '''
import numpy as np

from benchmark import traffic
from benchmark.reference import UNREACHED, Reference
from benchmark.runs import (Reservoir, Run, build_graph, closed_window,
                            traversed_edges)


def drive(ctx, short=False):
    n, src, dst = build_graph(ctx)
    engine = Reference(n, src, dst)
    requests = traffic.closed_requests(
        ctx.params, np.bincount(src, minlength=n), ctx.seed)

    def ask(sources):
        dist = engine.distances(sources)
        for d in dist:
            last = d[d != UNREACHED].max()
            if short and last > 0:
                d[d == last] = UNREACHED
        return dist, np.stack([engine.parents(d, int(s))
                               for s, d in zip(sources, dist)])

    ask(requests[0])
    run = Run(kind="fixture_plain")
    sample = Reservoir(ctx.params["check_trees"],
                       traffic.rng(ctx.seed, traffic.SAMPLE))
    sent = closed_window(ctx, run, requests, ask, sample)
    ref = Reference(n, src, dst)
    run.traversed_edges = traversed_edges(ref, requests, sent)
    wrong = bad = 0
    for root, dist, parent in sample.items:
        w, b = ref.tree_faults(root, ref.distances([root])[0], dist, parent)
        wrong, bad = wrong + w, bad + b
    run.checks = {"wrong_distances": {"value": wrong, "limit": 0},
                  "bad_parents": {"value": bad, "limit": 0},
                  "unchecked": {"value": 0 if sample.items else 1, "limit": 0}}
    return run


def control():
    return {"short": True}
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


@pytest.mark.parametrize(
    "workload", [w["name"] for w in harness.load_benchmark()["workloads"]])
def test_each_cell_has_its_metrics(workload):
    bench = harness.load_benchmark()
    for trace in (False, True):
        for m in harness.cell_metrics(bench, workload, trace):
            assert callable(harness.load_reader(m["name"]))


def _join(root: str, workload: str, *metrics: str) -> None:
    """Adds ``workload`` to the ``workloads`` lists of the named metrics."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in metrics:
            m["workloads"].append(workload)
    with open(path, "w") as f:
        json.dump(bench, f)


def test_a_second_kernel_reports_teps_as_files(tiny_root, run_cell):
    """A closed loop of another kind than ``closed_search``, on a graph
    maker without a scale, added as new files: it runs through the shared
    window, joins ``teps`` and the idle share by their ``workloads`` lists
    alone, and its control comes out not correct, with no file of the
    benchmark edited."""
    before = _digests(tiny_root)
    _write(tiny_root, "benchmark/graphs/fixture_ring.py", RING_GRAPH)
    _write(tiny_root, "benchmark/drivers/fixture_plain.py", PLAIN_DRIVER)
    _write(tiny_root, "benchmark/traffic/fixture_plain.json", json.dumps(
        {"driver": "fixture_plain", "keys": 8, "batch": 2,
         "structure_seed": 3, "check_trees": 4}))
    _write(tiny_root, "benchmark/configs/fixture-ring.json", json.dumps(
        {"name": "fixture-ring", "graph": {"generator": "fixture_ring",
                                           "vertices": 256}}))
    _add_entries(
        tiny_root,
        configs=[{"name": "fixture-ring", "source": "https://example.org",
                  "file": "benchmark/configs/fixture-ring.json",
                  "reduced": [], "why": "fixture"}],
        workloads=[{"name": "fixture.plain", "config": "fixture-ring",
                    "traffic": "fixture_plain", "chips": 1, "why": "fixture"}])
    _join(tiny_root, "fixture.plain", "teps", "device_idle_share.traversal")
    bench = harness.load_benchmark(tiny_root)
    assert [m["name"] for m in harness.cell_metrics(
        bench, "fixture.plain", True)] == ["device_idle_share.traversal"]

    res = run_cell("fixture.plain", seconds=0.3)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["attempted"] % 2 == 0
    assert set(res["metrics"]) == {"teps", "setup_s"}
    assert res["metrics"]["teps"]["value"] > 0
    ctl = run_cell("fixture.plain", seconds=0.3,
                   **harness.load_plugin("drivers", "fixture_plain",
                                         tiny_root).control())
    assert not ctl["correct"]
    assert ctl["checks"]["wrong_distances"]["value"] > 0
    after = _digests(tiny_root)
    assert {p: after[p] for p in before} == before


def test_the_bfs_cells_report_teps_and_setup():
    bench = harness.load_benchmark()
    for workload in ("g500-s20.single", "g500-s20.multi64"):
        assert {m["name"] for m in harness.cell_metrics(
            bench, workload, False)} == {"teps", "setup_s"}


def _json(root: str, rel: str) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def test_make_root_cuts_only_what_a_cell_declares(tmp_path):
    """A configuration without ``graph.scale`` and a traffic mix without
    ``batch``, ``keys`` or ``check_trees`` come through untouched; a
    driver's ``tiny`` makes its own cut; the BFS cells are cut as before."""
    repo = str(tmp_path / "repo")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(repo, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), repo)
    ring = {"name": "fixture-ring",
            "graph": {"generator": "fixture_ring", "vertices": 256}}
    bursts = {"driver": "fixture_bursts", "burst": 4, "period_s": 0.2}
    sweep = {"driver": "fixture_sweep", "sources": 64}
    _write(repo, "benchmark/graphs/fixture_ring.py", RING_GRAPH)
    _write(repo, "benchmark/drivers/fixture_bursts.py", BURST_DRIVER)
    _write(repo, "benchmark/drivers/fixture_sweep.py",
           "def tiny(params):\n    return {**params, 'sources': 2}\n")
    _write(repo, "benchmark/configs/fixture-ring.json", json.dumps(ring))
    _write(repo, "benchmark/traffic/fixture_bursts.json", json.dumps(bursts))
    _write(repo, "benchmark/traffic/fixture_sweep.json", json.dumps(sweep))
    _add_entries(
        repo,
        configs=[{"name": "fixture-ring", "source": "https://example.org",
                  "file": "benchmark/configs/fixture-ring.json",
                  "reduced": [], "why": "fixture"}],
        workloads=[{"name": "fixture.bursts", "config": "fixture-ring",
                    "traffic": "fixture_bursts", "chips": 1, "why": "fixture"},
                   {"name": "fixture.sweep", "config": "fixture-ring",
                    "traffic": "fixture_sweep", "chips": 1, "why": "fixture"}])

    root = make_root(str(tmp_path / "root"), source=repo)
    assert _json(root, "benchmark/configs/fixture-ring.json") == ring
    assert _json(root, "benchmark/traffic/fixture_bursts.json") == bursts
    assert _json(root, "benchmark/traffic/fixture_sweep.json") == {
        **sweep, "sources": 2}
    g500 = _json(REPO, "benchmark/configs/graph500-bfs-s20.json")
    g500["graph"]["scale"] = 7
    assert _json(root, "benchmark/configs/graph500-bfs-s20.json") == g500
    for name, cut in [("graph500_keys", {"batch": 1, "keys": 4, "check_trees": 8}),
                      ("multi_batch", {"batch": 16, "keys": 64, "check_trees": 8})]:
        rel = f"benchmark/traffic/{name}.json"
        assert _json(root, rel) == {**_json(REPO, rel), **cut}
