"""Registry-resident semiring algorithms (ISSUE 16): serve/algo.py.

Covers: SSSP and CC answered from a :class:`GraphRegistry`'s resident
device operands with oracle-exact results; operand residency reuse (the
second traversal hits the resident entry instead of re-uploading); pull
vs push CC on the same registered graph; pin balance (no pin leaked by
the traversal); the engine-name guard; and SSSP's per-graph operands
held as a budgeted entry of their own.
"""

import importlib

import jax
import numpy as np
import pytest

from bfs_tpu.algo import edge_weights_np
from bfs_tpu.graph.generators import gnm_graph
from bfs_tpu.obs.registry import get_registry
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


@pytest.fixture
def push_on(monkeypatch):
    """Push tiers small enough for the test graph."""
    sssp_mod = importlib.import_module("bfs_tpu.algo.sssp")
    monkeypatch.setattr(sssp_mod, "PUSH_TIERS", ((16, 128), (256, 2048)))
    monkeypatch.setattr(sssp_mod, "PUSH_MIN_SHRINK", 1)


def _entry_bytes(registry, name, engine):
    key = next(k for k in registry._resident if k[:3] == (name, 0, engine))
    return registry._resident[key]


@pytest.mark.parametrize("packed,engine", [
    (None, "sssp/w31"), (False, "sssp/w31/push"),
])
def test_registry_sssp_operands_are_budgeted(push_on, graph, packed,
                                             engine):
    # SSSP's per-graph operands (hash weights, dst_ends, and the out-row
    # view where the unpacked carry pushes) are a resident entry of their
    # own, counted in the budget at the bytes they hold.
    reg = GraphRegistry(device_budget_bytes=1 << 30)
    reg.register("g", graph)
    res = registry_sssp(reg, "g", SOURCE, max_weight=MAXW, packed=packed)
    assert res.packed == (packed is None)
    assert (res.push_rounds > 0) == (packed is False)
    nbytes, ops = _entry_bytes(reg, "g", engine)
    assert (ops.out is not None) == (packed is False)
    assert nbytes == sum(int(a.nbytes) for a in jax.tree.leaves(ops))
    push_bytes, _ = _entry_bytes(reg, "g", "push")
    assert reg.resident_bytes() == push_bytes + nbytes
    # A second query builds nothing and reads the same entry.
    built = get_registry().count("sssp_operands_built")
    again = registry_sssp(reg, "g", SOURCE, max_weight=MAXW, packed=packed)
    assert get_registry().count("sssp_operands_built") == built
    assert _entry_bytes(reg, "g", engine)[1] is ops
    np.testing.assert_array_equal(again.dist, res.dist)


def test_registry_sssp_evicts_within_budget(push_on, graph):
    # A budget that holds one graph's push and SSSP entries: an SSSP query
    # on a second graph evicts the first graph's, so the resident bytes
    # never pass the budget once the pins drop.
    probe = GraphRegistry()
    probe.register("a", graph)
    registry_sssp(probe, "a", SOURCE, max_weight=MAXW, packed=False)
    one = probe.resident_bytes()
    reg = GraphRegistry(device_budget_bytes=one)
    reg.register("a", graph)
    reg.register("b", graph)
    registry_sssp(reg, "a", SOURCE, max_weight=MAXW, packed=False)
    assert reg.resident_bytes() == one
    res = registry_sssp(reg, "b", SOURCE, max_weight=MAXW, packed=False)
    assert reg.resident_bytes() <= one
    assert {k[0] for k in reg.resident_keys()} == {"b"}
    assert reg.evictions == 2
    w = edge_weights_np(graph.src, graph.dst, MAXW)
    np.testing.assert_array_equal(res.dist, dijkstra(graph, w, SOURCE)[0])
