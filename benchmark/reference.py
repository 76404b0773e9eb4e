"""The plain reference: breadth-first search in NumPy, and the checks that
hold the program's answers to it.

It imports nothing of the program and reads only the edge list that the
configuration's graph maker made.  Distances come from a
level-synchronous search that carries 64 sources at once, one bit each
in a ``uint64`` word per vertex: a vertex joins the next level when one
of its neighbours is in the frontier and it has not been visited.  The graph is undirected (every
edge is stored both ways), so a vertex's neighbours are the targets of its
own out-edges.
"""

from __future__ import annotations

import numpy as np

#: Distance the program reports for a vertex it did not reach.
UNREACHED = np.iinfo(np.int32).max
#: Parent the program reports for a vertex it did not reach.
NO_PARENT = -1


class Reference:
    """CSR adjacency of one undirected graph and searches over it."""

    def __init__(self, num_vertices: int, src: np.ndarray, dst: np.ndarray):
        from scipy.sparse import csr_matrix

        n = int(num_vertices)
        # Repeated edges merge into one adjacency entry; the raw source
        # column stays for counting traversed input edges.
        adj = csr_matrix((np.ones(src.shape[0], np.int8), (src, dst)),
                         shape=(n, n))
        self.num_vertices = n
        self.edge_src = np.asarray(src)
        self.indptr = adj.indptr.astype(np.int64)
        self.indices = adj.indices.astype(np.int32)
        self.degree = np.diff(self.indptr)
        self.src = np.repeat(np.arange(n, dtype=np.int32), self.degree)
        self._adj = adj
        self._labels = None

    # -------------------------------------------------------- searches --
    def distances(self, roots) -> np.ndarray:
        """int32[R, V] hop distances from each root, ``UNREACHED`` where
        a vertex is not reached."""
        roots = np.asarray(roots, dtype=np.int64).reshape(-1)
        out = np.full((roots.shape[0], self.num_vertices), UNREACHED,
                      dtype=np.int32)
        for lo in range(0, roots.shape[0], 64):
            self._search64(roots[lo:lo + 64], out[lo:lo + 64])
        return out

    def _search64(self, roots: np.ndarray, out: np.ndarray) -> None:
        n = self.num_vertices
        nonempty = self.degree > 0
        starts = self.indptr[:-1][nonempty]
        frontier = np.zeros(n, np.uint64)
        for i, r in enumerate(roots.tolist()):
            frontier[r] |= np.uint64(1) << np.uint64(i)
            out[i, r] = 0
        visited = frontier.copy()
        level = 0
        while frontier.any():
            level += 1
            reach = np.zeros(n, np.uint64)
            if starts.size:
                reach[nonempty] = np.bitwise_or.reduceat(
                    frontier[self.indices], starts)
            new = reach & ~visited
            visited |= new
            bits = np.unpackbits(new.view(np.uint8).reshape(n, 8), axis=1,
                                 bitorder="little")
            vert, tree = np.nonzero(bits[:, :roots.shape[0]])
            out[tree, vert] = level
            frontier = new

    def parents(self, dist: np.ndarray, root: int) -> np.ndarray:
        """A parent for every vertex that ``dist`` reaches: a neighbour
        one level closer to ``root``."""
        parent = np.full(self.num_vertices, NO_PARENT, np.int32)
        reached = dist != UNREACHED
        closer = reached[self.src] & (dist[self.src] == dist[self.indices] - 1)
        parent[self.indices[closer]] = self.src[closer]
        parent[root] = root
        return parent

    # ------------------------------------------------------ components --
    def component_labels(self) -> np.ndarray:
        """Connected-component id of every vertex."""
        if self._labels is None:
            from scipy.sparse.csgraph import connected_components

            _, self._labels = connected_components(self._adj, directed=False)
        return self._labels

    def traversed_edges(self, roots) -> np.ndarray:
        """Graph500 traversed-edge count of a search from each root: the
        directed edges whose source the search reaches, halved, since each
        input edge is stored both ways; at least 1.  A search reaches
        exactly its root's connected component."""
        labels = self.component_labels()
        per_comp = np.bincount(labels[self.edge_src],
                               minlength=labels.max() + 1)
        return np.maximum(per_comp[labels[np.asarray(roots)]] // 2, 1)

    # ---------------------------------------------------------- checks --
    def tree_faults(self, root: int, want: np.ndarray, dist: np.ndarray,
                    parent: np.ndarray) -> tuple[int, int]:
        """``(wrong distances, bad parents)`` of one search's answer.

        Distances must equal the reference's.  The parents must form a
        BFS tree, as the Graph500 validation asks: the root is its own
        parent, an unreached vertex has none, and every other reached
        vertex's parent is a neighbour one level closer to the root."""
        n = self.num_vertices
        dist = np.asarray(dist)
        parent = np.asarray(parent)
        if dist.shape != (n,) or parent.shape != (n,):
            return n, n
        wrong = int(np.count_nonzero(dist != want))
        reached = want != UNREACHED
        bad = np.zeros(n, bool)
        bad[~reached] = parent[~reached] != NO_PARENT
        bad[root] = parent[root] != root
        inner = reached.copy()
        inner[root] = False
        p = parent[inner]
        in_range = (p >= 0) & (p < n)
        ok = in_range.copy()
        ok[in_range] = want[p[in_range]] == want[inner][in_range] - 1
        is_edge = np.zeros(n, bool)
        match = self.indices == parent[self.src]
        is_edge[self.src[match]] = True
        bad[inner] = ~(ok & is_edge[inner])
        return wrong, int(np.count_nonzero(bad))
