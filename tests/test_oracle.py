"""Sequential oracle tests: distances/parents/paths on the paper's worked
example (docs/BigData_Project.pdf §1.2 Table 2), check() invariants
(BreadthFirstPaths.java:172-221 semantics), multi-source, native parity."""

import numpy as np
import pytest

from bfs_tpu.graph.csr import Graph, INF_DIST, NO_PARENT
from bfs_tpu.graph.generators import gnm_graph, path_graph, rmat_graph
from bfs_tpu.graph.vertex import path_to
from bfs_tpu.oracle.bfs import canonical_bfs, check, dist_to, has_path_to, queue_bfs
from bfs_tpu.oracle.native import native_available, native_bfs, native_check

TINY_DIST = [0, 1, 1, 2, 2, 1]
TINY_PARENT = [0, 0, 0, 2, 2, 0]  # canonical min-parent


def test_queue_bfs_tiny(tiny_graph):
    dist, parent = queue_bfs(tiny_graph, 0)
    assert dist.tolist() == TINY_DIST
    assert parent.tolist() == TINY_PARENT  # sorted adjacency makes these agree
    assert check(tiny_graph, dist, parent, 0) == []


def test_canonical_bfs_tiny(tiny_graph):
    dist, parent = canonical_bfs(tiny_graph, 0)
    assert dist.tolist() == TINY_DIST
    assert parent.tolist() == TINY_PARENT
    # Paper Table 2: path to 3 is "0,5,3 or 0,2,3 depending on the order";
    # the canonical min-parent rule makes it deterministically 0-2-3.
    assert path_to(parent, 3) == [0, 2, 3]
    assert path_to(parent, 4) == [0, 2, 4]


def test_query_api(tiny_graph):
    dist, parent = queue_bfs(tiny_graph, 0)
    assert has_path_to(dist, 3)
    assert dist_to(dist, 3) == 2
    assert path_to(parent, 0) == [0]


def test_disconnected():
    g = Graph.from_undirected_edges(5, np.array([[0, 1], [2, 3]]))
    dist, parent = queue_bfs(g, 0)
    assert dist[2] == INF_DIST and dist[4] == INF_DIST
    assert parent[2] == NO_PARENT
    assert not has_path_to(dist, 4)
    assert path_to(parent, 4) == []
    # check() must flag nothing: unreached vertices are legal (Color.java:13-16).
    assert check(g, dist, parent, 0) == []


def test_multi_source():
    g = path_graph(10)
    dist, parent = queue_bfs(g, [0, 9])
    # BreadthFirstPaths multi-source semantics: dist to the NEAREST source.
    assert dist.tolist() == [0, 1, 2, 3, 4, 4, 3, 2, 1, 0]
    assert check(g, dist, parent, [0, 9]) == []


def test_canonical_vs_queue_distances_agree():
    for seed in range(5):
        g = gnm_graph(200, 500, seed=seed)
        d1, p1 = queue_bfs(g, 0)
        d2, p2 = canonical_bfs(g, 0)
        np.testing.assert_array_equal(d1, d2)
        assert check(g, d2, p2, 0) == []


def test_check_catches_corruption(tiny_graph):
    dist, parent = queue_bfs(tiny_graph, 0)
    bad = dist.copy()
    bad[3] = 7  # violates triangle inequality
    assert check(tiny_graph, bad, parent, 0) != []
    bad2 = dist.copy()
    bad2[0] = 1  # source distance must be 0
    assert check(tiny_graph, bad2, parent, 0) != []
    badp = parent.copy()
    badp[3] = 1  # 1-3 is not an edge / wrong level
    assert check(tiny_graph, dist, badp, 0) != []


@pytest.mark.skipif(not native_available(), reason="no C++ toolchain")
class TestNativeOracle:
    def test_native_matches_python_queue(self, tiny_graph):
        dist, parent, levels = native_bfs(tiny_graph, 0, policy="queue")
        d, p = queue_bfs(tiny_graph, 0)
        np.testing.assert_array_equal(dist, d)
        np.testing.assert_array_equal(parent, p)
        assert levels == 2

    def test_native_canonical_matches(self):
        for seed in range(3):
            g = rmat_graph(7, 4, seed=seed)
            dist, parent, _ = native_bfs(g, 0, policy="canonical")
            d, p = canonical_bfs(g, 0)
            np.testing.assert_array_equal(dist, d)
            np.testing.assert_array_equal(parent, p)

    def test_native_check(self, tiny_graph):
        dist, parent, _ = native_bfs(tiny_graph, 0)
        assert native_check(tiny_graph, dist, parent, 0) == 0
        bad = dist.copy()
        bad[0] = 5
        assert native_check(tiny_graph, bad, parent, 0) != 0

    def test_native_multi_source(self):
        g = path_graph(10)
        dist, _, levels = native_bfs(g, [0, 9])
        assert dist.tolist() == [0, 1, 2, 3, 4, 4, 3, 2, 1, 0]
        assert levels == 4


def test_check_directed_graph_no_false_positive():
    # A correct BFS over a directed graph must not trip the reachability
    # check: unreachable->reachable directed edges are legal.
    g = Graph.from_directed_edges(3, np.array([[0, 1], [2, 1]]))
    dist, parent = queue_bfs(g, 0)
    assert dist.tolist() == [0, 1, INF_DIST]
    assert check(g, dist, parent, 0) == []


# ------------------------------------------------ float32 SSSP oracle --

def _float_diamond():
    # 0-1 and 0-2 of 0.1 and 0.3, 1-2 of 0.2, 2-3 of 0.7: float32 decides
    # whether 0-1-2 beats 0-2, as on the device.
    from bfs_tpu.oracle.sssp import dijkstra

    edges = np.array([[0, 1], [0, 2], [1, 2], [2, 3]])
    w = np.array([0.1, 0.3, 0.2, 0.7], np.float32)
    g = Graph.from_undirected_edges(5, edges, weight=w)
    return g, dijkstra(g, g.weight, 0)


def test_dijkstra_float32_adds_in_float32():
    g, (dist, parent) = _float_diamond()
    f = np.float32
    via1 = f(0.1) + f(0.2)
    want2 = min(via1, f(0.3))
    assert dist.dtype == np.float32
    assert dist[2].view(np.uint32) == want2.view(np.uint32)
    assert dist[3].view(np.uint32) == (want2 + f(0.7)).view(np.uint32)
    assert np.isinf(dist[4]) and parent[4] == NO_PARENT
    assert parent[0] == 0 and parent[3] == 2


def test_check_sssp_float_tree_rule():
    from bfs_tpu.oracle.sssp import check_sssp

    g, (dist, parent) = _float_diamond()
    assert check_sssp(g, g.weight, dist, parent, 0) == []
    up = dist.copy()
    up[3] = np.nextafter(up[3], np.float32(np.inf))  # one ulp too large
    assert check_sssp(g, g.weight, up, parent, 0)
    missing = dist.copy()
    missing[3] = np.inf  # reachable, reported unreached
    assert check_sssp(g, g.weight, missing, parent, 0)
    cycle = parent.copy()
    cycle[1], cycle[2] = 2, 1
    assert any("chain" in v or "tight" in v
               for v in check_sssp(g, g.weight, dist, cycle, 0))
    stray = parent.copy()
    stray[4] = 3  # an unreached vertex with a parent
    assert check_sssp(g, g.weight, dist, stray, 0)


def test_check_sssp_finds_a_tied_parent_cycle():
    # 1 and 2 tie through a zero-weight edge: parents pointing at each
    # other are tight edges but no tree.
    from bfs_tpu.oracle.sssp import check_sssp, dijkstra

    edges = np.array([[0, 4], [0, 3], [4, 1], [3, 2], [1, 2]])
    w = np.array([0.5, 0.5, 0.25, 0.25, 0.0], np.float32)
    g = Graph.from_undirected_edges(5, edges, weight=w)
    dist, parent = dijkstra(g, g.weight, 0)
    assert check_sssp(g, g.weight, dist, parent, 0) == []
    cyc = parent.copy()
    cyc[1], cyc[2] = 2, 1
    assert any("chain" in v for v in check_sssp(g, g.weight, dist, cyc, 0))


def test_dijkstra_integer_weights_stay_at_least_one():
    from bfs_tpu.oracle.sssp import dijkstra

    g = path_graph(4)
    with pytest.raises(ValueError):
        dijkstra(g, np.zeros(g.num_edges, np.int32), 0)
    dist, _ = dijkstra(g, np.ones(g.num_edges, np.int32), 0)
    assert dist.dtype == np.int32 and dist.tolist() == [0, 1, 2, 3]
