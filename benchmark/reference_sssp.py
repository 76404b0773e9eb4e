"""The plain reference of Graph500 kernel 3, single-source shortest paths
over float32 weights, in NumPy, and the checks that hold the program's
answers to it.

It imports nothing of the program and reads only the weighted edge list
that the configuration's graph maker made: every input edge stored both
ways with its weight.  A distance is the float32 sum of a path's weights,
added left to right from the key, and the shortest distance is the least
such sum; an unreached vertex reads ``+inf`` with parent -1.

**Graph500's validation, exact in float32.**  :meth:`ReferenceSssp.faults`
counts, for one search's answer:

- ``relaxable_edges``: directed edges ``(u, v)`` with ``u`` reached and
  ``dist[v] > float32(dist[u] + w)`` (or ``dist[v]`` not a number);
- ``bad_tree_edges``: reached vertices other than the key whose parent
  edge is not an edge, or not tight (``dist[v] != float32(dist[p] + w)``
  for every parallel edge ``(p, v)``), a key that is not its own parent,
  and unreached vertices with a parent;
- ``unrooted_vertices``: reached vertices whose parent chain does not
  reach the key, and a key whose distance is not 0.

The counts are exact, not a tolerance.  Float32 addition of a weight
``w >= 0`` is monotone and never lowers its argument.  An answer with no
count is realised: each reached vertex's distance is the float32 sum
along its tree path from the key, so it is at least the least path sum.
And no edge improves it, so by induction along any path ``p`` from the
key (``dist[v] <= float32(dist[u] + w) <= float32(sum(p') + w)``) it is at
most every path's sum.  So it is the float32 shortest distance, bit for
bit, and a reachable vertex reported unreached leaves a relaxable edge.
On top, :meth:`ReferenceSssp.search` recomputes whole trees by a
float32 frontier Bellman-Ford, and ``wrong_distances`` compares those bit
for bit.
"""

from __future__ import annotations

import numpy as np

#: Distance of a vertex a search did not reach.
UNREACHED = np.float32(np.inf)
#: Parent of a vertex a search did not reach.
NO_PARENT = -1


def bfloat16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (nearest, ties to even) and
    back: a distance computed in the next lower precision."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


class ReferenceSssp:
    """One weighted graph, as directed edges and by source (CSR)."""

    def __init__(self, num_vertices: int, src: np.ndarray, dst: np.ndarray,
                 weight: np.ndarray):
        n = int(num_vertices)
        self.num_vertices = n
        self.src = np.asarray(src, np.int64)
        self.dst = np.asarray(dst, np.int64)
        self.weight = np.asarray(weight, np.float32)
        order = np.argsort(self.src, kind="stable")
        self.out_dst = self.dst[order]
        self.out_w = self.weight[order]
        self.indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(self.src, minlength=n), out=self.indptr[1:])

    # -------------------------------------------------------- searches --
    def search(self, root: int) -> tuple[np.ndarray, np.ndarray]:
        """``(dist float32[V], parent int32[V])`` from ``root`` by frontier
        Bellman-Ford: each round relaxes the out-edges of the vertices the
        last round improved, in float32, and a vertex's parent is the
        source that last improved it strictly (a tree: strict improvement
        through weights >= 0 cannot close a cycle)."""
        n = self.num_vertices
        dist = np.full(n, UNREACHED, np.float32)
        parent = np.full(n, NO_PARENT, np.int32)
        dist[root] = 0
        parent[root] = root
        frontier = np.array([root], np.int64)
        while frontier.size:
            lo, hi = self.indptr[frontier], self.indptr[frontier + 1]
            count = hi - lo
            edge = np.repeat(lo - np.cumsum(count) + count, count) + np.arange(
                count.sum())
            s = np.repeat(frontier, count)
            t = self.out_dst[edge]
            cand = dist[s] + self.out_w[edge]
            best = np.full(n, UNREACHED, np.float32)
            np.minimum.at(best, t, cand)
            improved = best < dist
            win = improved[t] & (cand == best[t])
            parent[t[win]] = s[win]
            dist = np.where(improved, best, dist)
            frontier = np.flatnonzero(improved)
        return dist, parent

    # ---------------------------------------------------------- checks --
    def faults(self, root: int, dist: np.ndarray,
               parent: np.ndarray) -> dict[str, int]:
        """Graph500's validation of one search's answer (module doc)."""
        n = self.num_vertices
        dist = np.asarray(dist)
        parent = np.asarray(parent)
        if dist.shape != (n,) or parent.shape != (n,) \
                or dist.dtype != np.float32:
            return {"relaxable_edges": n, "bad_tree_edges": n,
                    "unrooted_vertices": n}
        parent = parent.astype(np.int64)
        reached = np.isfinite(dist)
        du = dist[self.src]
        sums = du + self.weight
        from_reached = np.isfinite(du)
        relaxable = from_reached & ~(dist[self.dst] <= sums)

        tree_edge = (from_reached & (parent[self.dst] == self.src)
                     & (sums == dist[self.dst]))
        tight = np.zeros(n, bool)
        tight[self.dst[tree_edge]] = True
        inner = reached.copy()
        inner[root] = False
        bad = int(np.count_nonzero(inner & ~tight))
        bad += int(np.count_nonzero(~reached & (parent != NO_PARENT)))
        bad += int(parent[root] != root)

        # Pointer doubling along tight parent edges: after bit_length(V)
        # squarings every chain has reached its end, the key or a fault.
        up = np.where(tight, parent, np.arange(n))
        up[root] = root
        for _ in range(max(n, 2).bit_length()):
            up = up[up]
        unrooted = int(np.count_nonzero(inner & (up != root)))
        unrooted += int(dist[root] != 0)
        return {"relaxable_edges": int(np.count_nonzero(relaxable)),
                "bad_tree_edges": bad, "unrooted_vertices": unrooted}


def check_trees(ref: ReferenceSssp, items: list, recompute: int = 2) -> dict:
    """The validation of every sampled ``(root, dist, parent)``, and the
    first ``recompute`` of them compared whole, bit for bit, with the
    reference's own search.  Each count has limit 0."""
    totals = {"wrong_distances": 0, "relaxable_edges": 0,
              "bad_tree_edges": 0, "unrooted_vertices": 0}
    for i, (root, dist, parent) in enumerate(items):
        for name, value in ref.faults(root, dist, parent).items():
            totals[name] += value
        if i < recompute:
            want, _ = ref.search(root)
            got = np.asarray(dist)
            totals["wrong_distances"] += (
                int(np.count_nonzero(got.view(np.uint32)
                                     != want.view(np.uint32)))
                if got.shape == want.shape and got.dtype == np.float32
                else ref.num_vertices)
    checks = {name: {"value": value, "limit": 0}
              for name, value in totals.items()}
    checks["unchecked"] = {"value": 0 if items else 1, "limit": 0}
    return checks
