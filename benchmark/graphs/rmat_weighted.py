"""R-MAT graphs with Graph500 kernel 3's edge weights.

``edges(graph)`` is the plain R-MAT edge list of ``rmat.py`` for the same
parameters, so the BFS and SSSP configurations of one ``graph_seed`` and
scale search one graph.  ``weighted_edges(graph)`` adds one float32 weight
per input edge, uniform in [0, 1), as the Graph500 specification draws
them for kernel 3: a repeated edge has a weight of its own.  The weights
are drawn on the device from a key derived from ``graph_seed``, so every
run of a configuration has the same weights.
"""

from __future__ import annotations

import numpy as np

from benchmark.graphs.rmat import rmat_edges

#: ``jax.random.fold_in(key(graph_seed), WEIGHT_STREAM)`` draws the
#: weights, a stream apart from the one that draws the edges.
WEIGHT_STREAM = 3


def edges(graph: dict):
    """The configuration's undirected edge list as ``(num_vertices, u, v)``."""
    u, v = rmat_edges(graph["scale"], graph["edge_factor"], graph["a"],
                      graph["b"], graph["c"], graph["graph_seed"])
    return 1 << int(graph["scale"]), u, v


def weighted_edges(graph: dict):
    """``(num_vertices, u, v, w)``: the edges of :func:`edges` and a float32
    weight per input edge, uniform in [0, 1)."""
    import jax

    n, u, v = edges(graph)
    key = jax.random.fold_in(jax.random.key(int(graph["graph_seed"])),
                             WEIGHT_STREAM)
    w = jax.device_get(jax.random.uniform(key, u.shape, np.float32))
    return n, u, v, np.asarray(w, np.float32)
