"""The inputs of a run: the search keys deterministic in the seed, with
every seed doing the same searches, and the graph fixed by its
configuration."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import harness, traffic

DEGREE = np.array([0, 3, 1, 0, 2, 5, 1, 1, 0, 4] * 100)
BIG_SEED = 2**33 + 12345


@pytest.mark.parametrize("batch", [1, 8])
def test_closed_requests_depend_only_on_the_seed(batch):
    """Every seed searches the same keys in the same batches, in its own
    order."""
    params = {"keys": 64, "batch": batch, "structure_seed": 3}
    a = traffic.closed_requests(params, DEGREE, BIG_SEED)
    b = traffic.closed_requests(params, DEGREE, BIG_SEED)
    c = traffic.closed_requests(params, DEGREE, BIG_SEED + 1)
    assert a.shape == (64 // batch, batch)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    keys = a.reshape(-1)
    assert len(set(keys.tolist())) == 64 and (DEGREE[keys] > 0).all()
    assert sorted(map(sorted, a.tolist())) == sorted(map(sorted, c.tolist()))
    other = traffic.closed_requests(dict(params, structure_seed=4), DEGREE, BIG_SEED)
    assert set(other.reshape(-1).tolist()) != set(keys.tolist())


def test_rmat_graph_is_fixed_by_its_seed():
    rmat = harness.load_plugin("graphs", "rmat")
    graph = {"scale": 8, "edge_factor": 4, "a": 0.57, "b": 0.19, "c": 0.19,
             "graph_seed": 3}
    n, u, v = rmat.edges(graph)
    _, u2, v2 = rmat.edges(graph)
    _, u3, _ = rmat.edges(dict(graph, graph_seed=4))
    assert n == 256 and u.shape == v.shape == (1024,)
    assert u.dtype == v.dtype == np.int32
    assert np.array_equal(u, u2) and np.array_equal(v, v2)
    assert not np.array_equal(u, u3)
    assert min(u.min(), v.min()) >= 0 and max(u.max(), v.max()) < n
    # Skewed degrees, and the hub's label permuted away from vertex 0,
    # where the quadrant draws alone would put it.
    degree = np.bincount(np.concatenate([u, v]), minlength=n)
    assert degree.max() > 4 * degree.mean()
    assert int(np.argmax(degree)) != 0
