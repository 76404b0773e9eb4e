"""Registry-resident semiring algorithms (ISSUE 16): serve/algo.py.

Covers: SSSP and CC answered from a :class:`GraphRegistry`'s resident
device operands with oracle-exact results; operand residency reuse (the
second traversal hits the resident entry instead of re-uploading); pull
vs push CC on the same registered graph; pin balance (no pin leaked by
the traversal); and the engine-name guard.
"""

import numpy as np
import pytest

from bfs_tpu.algo import edge_weights_np
from bfs_tpu.graph.generators import gnm_graph
from bfs_tpu.oracle import dijkstra, union_find_labels
from bfs_tpu.serve import GraphRegistry, registry_cc, registry_sssp

MAXW = 31
SOURCE = 3


@pytest.fixture(scope="module")
def graph():
    return gnm_graph(300, 2100, seed=5)


@pytest.fixture()
def registry(graph):
    reg = GraphRegistry()
    reg.register("g", graph)
    return reg


@pytest.mark.algo_smoke
def test_registry_sssp_oracle_exact(registry, graph):
    w = edge_weights_np(graph.src, graph.dst, MAXW)
    odist, opar = dijkstra(graph, w, SOURCE)
    res = registry_sssp(registry, "g", SOURCE, max_weight=MAXW)
    np.testing.assert_array_equal(res.dist, odist)
    np.testing.assert_array_equal(res.parent, opar)


@pytest.mark.algo_smoke
@pytest.mark.parametrize("engine", ["push", "pull"])
def test_registry_cc_oracle_exact(registry, graph, engine):
    oracle = union_find_labels(graph)
    res = registry_cc(registry, "g", engine=engine)
    assert res.engine == engine
    np.testing.assert_array_equal(res.label, oracle)


def test_registry_operands_stay_resident(registry):
    registry_sssp(registry, "g", SOURCE, max_weight=MAXW)
    assert ("g", 0, "push") in registry.resident_keys()
    first = registry.acquire("g", "push")
    registry_cc(registry, "g")  # rides the SAME resident push operands
    assert registry.acquire("g", "push") is first
    assert registry.resident_keys().count(("g", 0, "push")) == 1


def test_registry_algo_leaves_no_pins(registry):
    registry_sssp(registry, "g", SOURCE, max_weight=MAXW)
    registry_cc(registry, "g", engine="pull")
    assert registry.get("g").pins == 0


def test_registry_cc_rejects_unknown_engine(registry):
    with pytest.raises(ValueError, match="unknown engine"):
        registry_cc(registry, "g", engine="relay")


def test_registry_sssp_refuses_stored_weights(graph):
    # Registry SSSP runs on the endpoint hash: a graph that stores its own
    # weights would otherwise be answered with other weights.
    from bfs_tpu.graph.csr import Graph

    weighted = Graph(graph.num_vertices, graph.src, graph.dst,
                     np.ones(graph.num_edges, np.float32))
    reg = GraphRegistry()
    reg.register("w", weighted)
    with pytest.raises(ValueError, match="stores its weights"):
        registry_sssp(reg, "w", SOURCE)
    assert reg.get("w").pins == 0
