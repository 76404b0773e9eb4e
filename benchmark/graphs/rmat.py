"""R-MAT graphs (Chakrabarti et al., 2004), with the Graph500 generator's
parameters.

A graph maker is ``benchmark/graphs/<generator>.py``, named by a
configuration's ``graph.generator``; its ``edges(graph)`` returns the
undirected edge list ``(num_vertices, u, v)``.  The program receives that
edge list and nothing else, and the plain reference reads the same list.

This one draws the edges on the device in one jitted call from the
configuration's ``graph_seed``, so every run of a configuration has the
same graph and the program's layout bundle is found again on disk.
Graph parameters: ``scale``, ``edge_factor``, ``a``, ``b``, ``c`` and
``graph_seed``.
"""

from __future__ import annotations

import numpy as np


def rmat_edges(scale: int, edge_factor: int, a: float, b: float, c: float,
               graph_seed: int):
    """``(u, v)`` int32 arrays of ``edge_factor << scale`` undirected
    R-MAT edges, drawn on the default device.

    Each edge descends ``scale`` levels of the adjacency matrix, picking
    the quadrant with probabilities ``a``, ``b``, ``c`` and ``1-a-b-c``;
    the vertex labels are then permuted, as the Graph500 generator does,
    so that vertex ids carry no degree order.  Self-loops and repeated
    edges are kept, as in Graph500."""
    import jax
    import jax.numpy as jnp

    m = int(edge_factor) << int(scale)
    n = 1 << int(scale)

    @jax.jit
    def draw(key):
        k_levels, k_perm = jax.random.split(key)

        def level(carry, k):
            u, v = carry
            r = jax.random.uniform(k, (m,), jnp.float32)
            row = r >= a + b
            col = ((r >= a) & (r < a + b)) | (r >= a + b + c)
            return (u * 2 + row.astype(jnp.int32),
                    v * 2 + col.astype(jnp.int32)), None

        zero = jnp.zeros((m,), jnp.int32)
        (u, v), _ = jax.lax.scan(
            level, (zero, zero), jax.random.split(k_levels, scale))
        perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
        return perm[u], perm[v]

    u, v = jax.device_get(draw(jax.random.key(int(graph_seed))))
    return np.asarray(u, np.int32), np.asarray(v, np.int32)


def edges(graph: dict):
    """The configuration's undirected edge list as ``(num_vertices, u, v)``."""
    u, v = rmat_edges(graph["scale"], graph["edge_factor"], graph["a"],
                      graph["b"], graph["c"], graph["graph_seed"])
    return 1 << int(graph["scale"]), u, v
