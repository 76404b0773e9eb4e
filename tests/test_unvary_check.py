"""``compat.unvary`` replicates leaves that are equal on every shard with
``pmax``.  With ``compat.CHECK_REPLICATED`` set it also checks that they
are equal, so a shard that diverges fails here instead of being maxed."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from bfs_tpu.graph.generators import gnm_graph
from bfs_tpu.graph.relay import build_sharded_relay_graph
from bfs_tpu.oracle.bfs import canonical_bfs, queue_bfs
from bfs_tpu.parallel import compat
from bfs_tpu.parallel.grid import bfs_grid, make_grid_mesh
from bfs_tpu.parallel.sharded import bfs_sharded, make_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device virtual platform"
)


@pytest.fixture
def checking(monkeypatch):
    monkeypatch.setattr(compat, "CHECK_REPLICATED", True)
    jax.clear_caches()  # retrace: the switch is read at trace time
    yield
    jax.clear_caches()


def test_unvary_check_catches_divergence(checking):
    mesh = jax.make_mesh((8,), ("graph",))

    def body(x):
        return compat.unvary(x + jax.lax.axis_index("graph"), ("graph",))

    f = jax.jit(compat.shard_map(body, mesh=mesh, in_specs=P(),
                                 out_specs=P()))
    with pytest.raises(Exception, match="differs across shards"):
        jax.block_until_ready(f(jnp.int32(0)))


@pytest.mark.parametrize("mesh_kind", ["1d", "grid"])
def test_mesh_outputs_replicated(checking, mesh_kind):
    """The 1D and 2x4 programs with telemetry and auto direction: every
    leaf ``unvary`` replicates is equal on every shard."""
    g = gnm_graph(250, 1273, seed=1)
    srg = build_sharded_relay_graph(g, 8)
    kw = dict(telemetry=True, direction="auto", exchange="auto")
    if mesh_kind == "1d":
        res, curve = bfs_sharded(srg, 3, mesh=make_mesh(graph=8),
                                 engine="relay", **kw)
    else:
        res, curve = bfs_grid(srg, 3, mesh=make_grid_mesh(2, 4), **kw)
    assert curve["direction_schedule"]["schedule"]
    assert (res.dist == queue_bfs(g, 3)[0]).all()
    assert (res.parent == canonical_bfs(g, 3)[1]).all()
