"""The kernel 3 cell's own check: ``reference_sssp.py`` on small weighted
graphs, the ``closed_sssp`` control, the readers of the cell's metrics and
a traced run off the chip."""

from __future__ import annotations

import heapq
from types import SimpleNamespace

import numpy as np
import pytest
from harness_fixtures import run_cell, tiny_root  # noqa: F401 (fixtures)

from benchmark import harness
from benchmark.reference_sssp import (NO_PARENT, ReferenceSssp, bfloat16,
                                      check_trees)
from benchmark.runs import Run

CELL = "g500-s20.sssp"
COUNTS = ("relaxable_edges", "bad_tree_edges", "unrooted_vertices")


def _graph(n=200, m=900, seed=0, zeros=4):
    """``n`` vertices, the last 20 without edges, ``m`` input edges with
    float32 weights, the first ``zeros`` of them 0."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n - 20, m).astype(np.int32)
    v = rng.integers(0, n - 20, m).astype(np.int32)
    w = rng.random(m, dtype=np.float32)
    w[:zeros] = 0.0
    return (n, np.concatenate([u, v]), np.concatenate([v, u]),
            np.concatenate([w, w]))


def _dijkstra32(n, src, dst, w, root):
    """A second plain float32 shortest-path search: Dijkstra with a heap."""
    dist = np.full(n, np.inf, np.float32)
    dist[root] = 0
    heap = [(0.0, root)]
    done = np.zeros(n, bool)
    out = [[] for _ in range(n)]
    for s, t, x in zip(src.tolist(), dst.tolist(), w):
        out[s].append((t, x))
    while heap:
        _, a = heapq.heappop(heap)
        if done[a]:
            continue
        done[a] = True
        for t, x in out[a]:
            nd = dist[a] + x
            if nd < dist[t]:
                dist[t] = nd
                heapq.heappush(heap, (float(nd), t))
    return dist


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_search_is_float32_shortest(seed):
    n, src, dst, w = _graph(seed=seed)
    ref = ReferenceSssp(n, src, dst, w)
    root = int(src[0])
    dist, parent = ref.search(root)
    want = _dijkstra32(n, src, dst, w, root)
    np.testing.assert_array_equal(dist.view(np.uint32), want.view(np.uint32))
    assert np.isinf(dist).any() and (parent[np.isinf(dist)] == NO_PARENT).all()
    assert ref.faults(root, dist, parent) == dict.fromkeys(COUNTS, 0)


def _sound():
    n, src, dst, w = _graph(seed=3)
    ref = ReferenceSssp(n, src, dst, w)
    root = int(src[0])
    dist, parent = ref.search(root)
    return ref, root, dist, parent


def test_sound_answer_reads_zero():
    ref, root, dist, parent = _sound()
    checks = check_trees(ref, [(root, dist, parent)] * 3)
    assert all(c["value"] == 0 and c["limit"] == 0 for c in checks.values())
    assert set(checks) == {"wrong_distances", *COUNTS, "unchecked"}
    assert check_trees(ref, [])["unchecked"]["value"] == 1


def _leaf(parent, dist):
    """A reached vertex that is no one's parent."""
    reached = np.flatnonzero(np.isfinite(dist) & (dist > 0))
    return int(np.setdiff1d(reached, parent)[0])


def _faulty(kind, dist, parent):
    dist, parent = dist.copy(), parent.copy()
    if kind == "bfloat16":
        dist = bfloat16(dist)
    elif kind == "cycle":
        v = _leaf(parent, dist)
        p = int(parent[v])
        parent[p] = v  # p's chain now runs p -> v -> p
    elif kind == "missing":
        v = _leaf(parent, dist)
        dist[v] = np.inf
        parent[v] = NO_PARENT
    elif kind == "one_ulp":
        v = _leaf(parent, dist)
        dist[v] = np.nextafter(dist[v], np.float32(np.inf))
    return dist, parent


@pytest.mark.parametrize("kind", ["bfloat16", "cycle", "missing", "one_ulp"])
def test_faulty_answers_read_non_zero(kind):
    ref, root, dist, parent = _sound()
    bad_dist, bad_parent = _faulty(kind, dist, parent)
    faults = ref.faults(root, bad_dist, bad_parent)
    assert sum(faults.values()) > 0, faults
    checks = check_trees(ref, [(root, bad_dist, bad_parent)])
    assert not harness.checks_passed(checks)
    # A distance off where it was computed also reads bit for bit.
    if kind != "cycle":
        assert checks["wrong_distances"]["value"] > 0


def test_bfloat16_rounds_to_nearest_even():
    x = np.array([1.0, 1.0 + 2.0 ** -8, 1.0 + 3 * 2.0 ** -8, np.inf, 0.0],
                 np.float32)
    np.testing.assert_array_equal(
        bfloat16(x), np.array([1.0, 1.0, 1.0 + 2.0 ** -6, np.inf, 0.0],
                              np.float32))


def test_wrong_shapes_and_dtypes_read_every_vertex():
    ref, root, dist, parent = _sound()
    n = ref.num_vertices
    assert ref.faults(root, dist[:-1], parent)["bad_tree_edges"] == n
    assert ref.faults(root, dist.astype(np.float64),
                      parent)["relaxable_edges"] == n


def test_control_reads_non_zero(run_cell):
    """The ``closed_sssp`` control, bfloat16 distances from the reference,
    comes out not correct through a whole run."""
    driver = harness.load_cell(harness.load_benchmark(), CELL)[2]["driver"]
    assert driver == "closed_sssp"
    ctl = run_cell(CELL, **harness.load_plugin("drivers", driver).control())
    assert not ctl["correct"]
    assert ctl["checks"]["bad_tree_edges"]["value"] > 0
    assert ctl["checks"]["wrong_distances"]["value"] > 0


def test_traced_run_off_the_chip(run_cell):
    """Off the chip the device readers find no TPU plane and are left out;
    the program's counter and span still read."""
    res = run_cell(CELL, trace=1)
    assert res["correct"], res["checks"]
    metrics = res["metrics"]
    assert "minplus_roofline.sssp" not in metrics
    assert "device_ms_per_search.sssp" not in metrics
    assert metrics["supersteps_per_search.sssp"]["value"] >= 1
    assert metrics["fetch_ms_per_search.sssp"]["value"] > 0


def _run(**kw):
    trace = SimpleNamespace(busy_s=2.0, idle_share=20.0, host=[],
                            window=(0.0, 2.5e9))
    base = dict(kind="closed_sssp", searches=4, traversed_edges=1000,
                trace=trace, peaks={"hbm_bytes_per_s": 1e6},
                facts={"num_vertices": 100, "batch": 1, "sssp_searches": 4,
                       "sssp_supersteps": 60})
    base.update(kw)
    return Run(**base)


def test_readers_of_the_cell():
    read = {m: harness.load_reader(m) for m in (
        "minplus_roofline.sssp", "device_ms_per_search.sssp",
        "supersteps_per_search.sssp")}
    run = _run()
    # 12 B x 2000 directed edges + 8 B x 100 vertices, per search x 4 ...
    want = 100.0 * (12 * 2 * 1000 + 8 * 100 * 4) / 1e6 / 2.0
    assert read["minplus_roofline.sssp"](run) == pytest.approx(want)
    assert read["device_ms_per_search.sssp"](run) == pytest.approx(500.0)
    assert read["supersteps_per_search.sssp"](run) == 15.0
    # ... and nothing where the run holds nothing to read.
    for name, fn in read.items():
        assert fn(_run(kind="closed_search")) is None, name
    assert read["minplus_roofline.sssp"](_run(peaks={})) is None
    assert read["supersteps_per_search.sssp"](_run(facts={})) is None
    no_plane = SimpleNamespace(busy_s=0.0, idle_share=None, host=[],
                               window=(0.0, 1.0))
    assert read["minplus_roofline.sssp"](_run(trace=no_plane)) is None
    assert read["device_ms_per_search.sssp"](_run(trace=no_plane)) is None
