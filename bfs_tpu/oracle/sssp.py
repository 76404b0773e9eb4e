"""Sequential SSSP oracle: binary-heap Dijkstra, and an invariant
verifier for any claimed result.

The correctness anchor for :mod:`bfs_tpu.algo.sssp`, playing the role
algs4's ``BreadthFirstPaths`` plays for BFS: a textbook host
implementation against which the device engines must be EXACT, plus a
:func:`check_sssp` verifier usable on any claimed result.

Weights are an explicit per-directed-edge array, aligned with
``graph.src``/``graph.dst`` — pass
:func:`bfs_tpu.algo.substrate.edge_weights_np` output for parity with the
device's hash weights, or a graph's stored weights.  Two arithmetics:

* **Integer weights (>= 1)** sum in int64 and give int32 distances.
  Parents use the device's rule: after the distances are final,
  ``parent[v] = min u`` over in-edges with ``dist[u] + w(u, v) ==
  dist[v]`` — a vectorized post-pass (``np.minimum.at``), NOT heap pop
  order, so parents are bit-exact across the host oracle and every
  device arm regardless of relaxation schedule.
* **Float weights (float32, finite, >= 0)** sum in float32, each addition
  rounded as the device rounds it, so the distances are bit-equal to the
  device's (float32 addition is monotone, so Dijkstra's argument holds
  and both find the least float32 path sum).  Unreached is ``+inf``.
  Parents are Dijkstra's own tree: the vertex whose pop last strictly
  improved the distance.  Under ties (a zero weight, or one below half
  an ulp of the distance) more than one tree is valid, so a float result
  is held to the tree rule of :func:`check_sssp`, not to these parents.
"""

from __future__ import annotations

import heapq

import numpy as np

from ..graph.csr import Graph, INF_DIST, NO_PARENT, check_weights

__all__ = ["dijkstra", "check_sssp"]


def dijkstra(graph: Graph, weights: np.ndarray, source: int = 0):
    """Single-source shortest paths.  Returns ``(dist, parent int32[V])``
    with ``parent[source] == source`` and NO_PARENT for unreached
    vertices: int32 ``dist`` (INF_DIST unreached) and canonical parents
    under integer weights (>= 1), float32 ``dist`` (``+inf`` unreached)
    and Dijkstra's tree under float32 weights (>= 0)."""
    v = graph.num_vertices
    if not (0 <= source < v):
        raise ValueError("source vertex out of range")
    weights = check_weights(weights, graph.num_edges)
    floating = weights.dtype == np.float32
    if not floating and graph.num_edges and int(weights.min()) < 1:
        raise ValueError("integer weights must be >= 1")
    # CSR over (dst, weight) per source vertex.
    order = np.argsort(graph.src, kind="stable")
    d_sorted = graph.dst[order]
    w_sorted = weights[order] if floating else weights[order].astype(np.int64)
    indptr = np.zeros(v + 1, dtype=np.int64)
    np.add.at(indptr, graph.src[order] + 1, 1)
    indptr = np.cumsum(indptr)

    far = np.float32(np.inf) if floating else np.iinfo(np.int64).max
    dist = np.full(v, far, dtype=w_sorted.dtype)
    tree = np.full(v, NO_PARENT, dtype=np.int32)
    done = np.zeros(v, dtype=bool)
    dist[source] = 0
    tree[source] = source
    heap = [(0, source)]
    while heap:
        _, u = heapq.heappop(heap)
        if done[u]:
            continue
        done[u] = True
        du = dist[u]
        for i in range(indptr[u], indptr[u + 1]):
            nd = du + w_sorted[i]  # float32 + float32 rounds to float32
            t = d_sorted[i]
            if nd < dist[t]:
                dist[t] = nd
                tree[t] = u
                heapq.heappush(heap, (nd.item(), int(t)))

    reached = dist != far
    if floating:
        return dist, tree
    if reached.any() and int(dist[reached].max()) >= INF_DIST:
        raise OverflowError("shortest distance exceeds int32 range")
    out = np.full(v, INF_DIST, dtype=np.int32)
    out[reached] = dist[reached].astype(np.int32)

    # Canonical parents: the same exit-time rule as the device
    # (algo/sssp.py::_sssp_parents) — min u among optimal predecessors.
    parent = np.full(v, INF_DIST, dtype=np.int64)
    sv, dv = graph.src.astype(np.int64), graph.dst.astype(np.int64)
    ok = (dist[sv] != far) & (dist[sv] + weights.astype(np.int64) == dist[dv])
    np.minimum.at(parent, dv[ok], sv[ok])
    parent = np.where(reached & (parent != INF_DIST), parent, NO_PARENT)
    parent = parent.astype(np.int32)
    parent[source] = source
    return out, parent


def _path_sums(dist: np.ndarray, weights: np.ndarray, sv, dv):
    """``dist[u] + w`` per edge in the weights' arithmetic (float32, or
    int64 for integers), with the unreached mask of ``dist[u]``."""
    if weights.dtype == np.float32:
        du = dist[sv].astype(np.float32)
        return du + weights, np.isfinite(du)
    du = dist[sv].astype(np.int64)
    return du + weights.astype(np.int64), du != INF_DIST


def check_sssp(
    graph: Graph,
    weights: np.ndarray,
    dist: np.ndarray,
    parent: np.ndarray,
    source: int = 0,
) -> list[str]:
    """SSSP optimality verifier; returns violations (empty = OK).

    The min-plus analog of the BFS ``check()``, in the weights' own
    arithmetic (float32 sums for float weights, exact for integers):
      1. the source has distance 0 and is its own parent;
      2. per directed edge (u, v): if u is reached, v is reached and
         ``dist[v] <= dist[u] + w`` (no relaxable edge remains);
      3. the parents form a shortest-path tree: every reached non-source
         v has a parent with ``dist[v] == dist[parent] + w`` on a real
         edge, its parent chain reaches the source, and an unreached
         vertex has parent NO_PARENT;
      4. under integer weights, a parent with a smaller distance is the
         canonical MINIMUM such optimal predecessor (the device's rule).
    Together 1–3 make every distance the least path sum: it is realised
    by the tree path, and no edge can lower it.
    """
    v = graph.num_vertices
    weights = check_weights(weights, graph.num_edges)
    floating = weights.dtype == np.float32
    dist = np.asarray(dist)[:v]
    parent = np.asarray(parent)[:v].astype(np.int64)
    reach = np.isfinite(dist) if floating else dist != INF_DIST
    violations: list[str] = []

    if dist[source] != 0 or parent[source] != source:
        violations.append(
            f"source {source}: distance {dist[source]}, parent "
            f"{parent[source]} (want 0 and itself)"
        )

    sv, dv = graph.src.astype(np.int64), graph.dst.astype(np.int64)
    sums, from_reached = _path_sums(dist, weights, sv, dv)
    for i in np.flatnonzero(from_reached & ~reach[dv])[:5]:
        violations.append(
            f"edge {sv[i]}->{dv[i]}: source reachable but destination is not"
        )
    slack = from_reached & reach[dv] & (dist[dv] > sums)
    for i in np.flatnonzero(slack)[:5]:
        violations.append(
            f"edge {sv[i]}->{dv[i]}: dist[{dv[i]}]={dist[dv[i]]} > "
            f"dist[{sv[i]}]+w={sums[i]}"
        )

    for w_ in np.flatnonzero(~reach & (parent != NO_PARENT))[:5]:
        violations.append(f"unreached vertex {w_} has parent {parent[w_]}")
    tight_edge = from_reached & (sums == dist[dv]) & (parent[dv] == sv)
    has_tree_edge = np.zeros(v, dtype=bool)
    has_tree_edge[dv[tight_edge]] = True
    non_src = np.flatnonzero(reach)
    non_src = non_src[non_src != source]
    for w_ in non_src[~has_tree_edge[non_src]][:5]:
        violations.append(
            f"vertex {w_}: parent {parent[w_]} is not a tight in-edge"
        )
    # Pointer doubling: after ceil(log2 V) + 1 squarings every chain of
    # valid parents has reached its end, which must be the source.
    root = np.where(has_tree_edge, parent, np.arange(v))
    root[source] = source
    for _ in range(max(v, 2).bit_length() + 1):
        root = root[root]
    for w_ in non_src[root[non_src] != source][:5]:
        violations.append(f"vertex {w_}: parent chain does not reach {source}")

    if not floating:
        # Canonical parent: the min optimal predecessor of smaller dist.
        canon = np.full(v, INF_DIST, dtype=np.int64)
        ok = from_reached & (sums == dist[dv]) & (dist[sv] < dist[dv])
        np.minimum.at(canon, dv[ok], sv[ok])
        good = non_src[(canon[non_src] != INF_DIST)]
        for w_ in good[parent[good] != canon[good]][:5]:
            violations.append(
                f"vertex {w_}: parent {parent[w_]} is not the canonical "
                f"min optimal predecessor {canon[w_]}"
            )
    return violations
