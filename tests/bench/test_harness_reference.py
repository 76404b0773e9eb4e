"""The plain reference against a breadth-first search written out in
Python, the Graph500 edge count against the repo's own, and the checks
against answers with planted faults."""

from __future__ import annotations

from collections import deque

import numpy as np
import pytest

from benchmark.reference import NO_PARENT, UNREACHED, Reference


def small_graph(seed: int, n: int = 300, m: int = 500):
    """A sparse random undirected graph with a few isolated vertices,
    self-loops and repeated edges."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n - 20, m).astype(np.int32)
    v = rng.integers(0, n - 20, m).astype(np.int32)
    u[:3], v[:3] = v[:3], v[:3]  # self-loops
    u = np.concatenate([u, u[:5]])
    v = np.concatenate([v, v[:5]])  # repeated edges
    return n, np.concatenate([u, v]), np.concatenate([v, u])


def loop_bfs(n, src, dst, root):
    adj = [[] for _ in range(n)]
    for a, b in zip(src.tolist(), dst.tolist()):
        adj[a].append(b)
    dist = [UNREACHED] * n
    dist[root] = 0
    q = deque([root])
    while q:
        x = q.popleft()
        for y in adj[x]:
            if dist[y] == UNREACHED:
                dist[y] = dist[x] + 1
                q.append(y)
    return np.array(dist, np.int64)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_distances_match_a_loop_bfs(seed):
    n, src, dst = small_graph(seed)
    ref = Reference(n, src, dst)
    roots = np.random.default_rng(seed).choice(n, 70, replace=False)
    got = ref.distances(roots)  # two groups of searches: 64 and 6
    for root, row in zip(roots.tolist(), got):
        assert np.array_equal(row, loop_bfs(n, src, dst, root))


def test_traversed_edges_match_graph500_run():
    from bfs_tpu.graph.csr import Graph
    from tools.graph500_run import traversed_edges

    n, src, dst = small_graph(4)
    ref = Reference(n, src, dst)
    graph = Graph(n, src, dst)
    roots = [0, 5, n - 1, n - 30]
    dists = ref.distances(roots)
    want = [traversed_edges(graph, d) for d in dists]
    assert ref.traversed_edges(roots).tolist() == want


def tree(ref, root):
    dist = ref.distances([root])[0]
    return dist, ref.parents(dist, root)


def test_a_true_tree_has_no_faults():
    n, src, dst = small_graph(5)
    ref = Reference(n, src, dst)
    for root in (0, 7, n - 1):
        dist, parent = tree(ref, root)
        assert ref.tree_faults(root, dist, dist, parent) == (0, 0)


@pytest.mark.parametrize("fault", ["dist", "parent_far", "parent_not_edge",
                                   "unreached_parent", "root_parent", "shape"])
def test_planted_faults_are_found(fault):
    n, src, dst = small_graph(6)
    ref = Reference(n, src, dst)
    root = int(np.argmax(ref.degree))
    want, parent = tree(ref, root)
    dist = want.copy()
    far = int(np.flatnonzero(want == want[want != UNREACHED].max())[0])
    if fault == "dist":
        dist[far] += 1
    elif fault == "parent_far":
        parent[far] = far  # not one level closer
    elif fault == "parent_not_edge":
        # One level closer, but not a neighbour.
        nbrs = set(ref.indices[ref.indptr[far]:ref.indptr[far + 1]].tolist())
        closer = np.flatnonzero(want == want[far] - 1).tolist()
        parent[far] = next(c for c in closer if c not in nbrs)
    elif fault == "unreached_parent":
        parent[int(np.flatnonzero(want == UNREACHED)[0])] = root
    elif fault == "root_parent":
        parent[root] = NO_PARENT
    elif fault == "shape":
        dist = dist[:-1]
    wrong, bad = ref.tree_faults(root, want, dist, parent)
    assert wrong + bad > 0
