"""Sharded SSSP and CC: the semiring supersteps over an edge-sharded mesh.

The exact shape of :func:`bfs_tpu.parallel.sharded._bfs_sharded_fused`
with the semiring swapped: each device holds one round-robin edge shard
(``build_device_graph(num_shards=n)``), per-vertex state is replicated,
per-shard candidates merge with ONE ``lax.pmin`` over the graph axis, and
every device then computes identical state updates — no further
collectives, the replicated-carry contract the BFS mesh programs
established (version-spanning via :mod:`bfs_tpu.parallel.compat`).

Sharded SSSP keeps the endpoint-hash weights
(:func:`bfs_tpu.algo.substrate.edge_weights`): each mesh body recomputes
its own shard's weights from the edge block it already holds, so
re-sharding can never misalign them (stored weights run on one device,
:func:`bfs_tpu.algo.sssp.sssp`).  The exit-time parent pass runs OUTSIDE
the mesh on the flat edge arrays (the replicated final dists make it
shard-count-independent by construction).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from ..analysis.runtime import traced
from ..graph.csr import Graph, build_device_graph
from ..parallel.compat import shard_map as _shard_map
from ..parallel.sharded import GRAPH_AXIS, make_mesh
from .cc import CcResult, CcState, cc_superstep, init_cc_state
from .sssp import (
    SsspResult,
    SsspState,
    _converged,
    _hash_weights,
    _parents_and_fetch,
    _rounds_cap,
    init_sssp_state,
    sssp_superstep,
)
from .substrate import DEFAULT_MAX_WEIGHT, edge_weights, resolve_delta


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "num_vertices", "max_weight", "delta", "max_rounds",
    ),
)
@traced("algo.sssp_sharded_fused")
def _sssp_sharded_fused(
    src, dst, source, *, mesh, num_vertices, max_weight, delta, max_rounds
):
    def inner(src_blk, dst_blk, source):
        src_e = src_blk.reshape(-1)
        dst_e = dst_blk.reshape(-1)
        w_e = edge_weights(src_e, dst_e, max_weight)
        state = init_sssp_state(num_vertices, source, delta)

        def cond(s):
            return s.changed & (s.rounds < max_rounds)

        def body(s):
            return sssp_superstep(
                s, src_e, dst_e, w_e, delta, axis_name=GRAPH_AXIS
            )

        return jax.lax.while_loop(cond, body, state)

    fn = _shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(GRAPH_AXIS, None), P(GRAPH_AXIS, None), P()),
        out_specs=SsspState(*(P(),) * len(SsspState._fields)),
        axis_names={GRAPH_AXIS},
    )
    return fn(src, dst, source)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "num_vertices", "max_rounds"),
)
@traced("algo.cc_sharded_fused")
def _cc_sharded_fused(src, dst, *, mesh, num_vertices, max_rounds):
    def inner(src_blk, dst_blk):
        src_e = src_blk.reshape(-1)
        dst_e = dst_blk.reshape(-1)
        state = init_cc_state(num_vertices)

        def cond(s):
            return s.changed & (s.rounds < max_rounds)

        def body(s):
            return cc_superstep(s, src_e, dst_e, axis_name=GRAPH_AXIS)

        return jax.lax.while_loop(cond, body, state)

    fn = _shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(GRAPH_AXIS, None), P(GRAPH_AXIS, None)),
        out_specs=CcState(P(), P(), P(), P()),
        axis_names={GRAPH_AXIS},
    )
    return fn(src, dst)


def sssp_sharded(
    graph: Graph,
    source: int = 0,
    *,
    num_shards: int | None = None,
    mesh=None,
    max_weight: int = DEFAULT_MAX_WEIGHT,
    delta: int | str | None = None,
    max_rounds: int | None = None,
    block: int = 1024,
) -> SsspResult:
    """Edge-sharded SSSP (unpacked carry).  ``num_shards`` defaults to
    the mesh's graph-axis extent; results are bit-identical to the
    single-device :func:`bfs_tpu.algo.sssp.sssp` unpacked arm — the pmin
    merge commutes with the segmented min."""
    if graph.weight is not None:
        raise ValueError("sharded SSSP runs on the endpoint hash; a graph "
                         "with stored weights runs on one device (sssp)")
    if mesh is None:
        mesh = make_mesh(graph=num_shards, batch=1)
    n_shards = mesh.shape[GRAPH_AXIS]
    dg = build_device_graph(graph, num_shards=n_shards, block=block)
    v = dg.num_vertices
    delta_i = resolve_delta(delta)
    cap = _rounds_cap(v, max_rounds)
    state = _sssp_sharded_fused(
        jnp.asarray(dg.src), jnp.asarray(dg.dst), jnp.int32(source),
        mesh=mesh, num_vertices=v, max_weight=max_weight,
        delta=delta_i, max_rounds=cap,
    )
    flat_src = jnp.asarray(np.ascontiguousarray(dg.src.reshape(-1)))
    flat_dst = jnp.asarray(np.ascontiguousarray(dg.dst.reshape(-1)))
    # The shards, laid end to end, are not dst-sorted as a whole: no
    # ``ends``, so the parent pass combines by the scatter.
    dist, parent, (rounds, changed) = _parents_and_fetch(
        state.dist, (state.rounds, state.changed), flat_src, flat_dst,
        _hash_weights(flat_src, flat_dst, max_weight=max_weight),
        None, source, v + 1,
    )
    rounds = int(rounds)
    _converged(changed, rounds, max_rounds, source)
    return SsspResult(
        dist=dist[:v], parent=parent[:v], rounds=rounds,
        max_weight=max_weight, delta=delta_i, packed=False,
    )


def cc_sharded(
    graph: Graph,
    *,
    num_shards: int | None = None,
    mesh=None,
    max_rounds: int | None = None,
    block: int = 1024,
) -> CcResult:
    """Edge-sharded connected components; labels bit-identical to the
    single-device push arm (one label fixpoint)."""
    if mesh is None:
        mesh = make_mesh(graph=num_shards, batch=1)
    n_shards = mesh.shape[GRAPH_AXIS]
    dg = build_device_graph(graph, num_shards=n_shards, block=block)
    v = dg.num_vertices
    cap = int(max_rounds) if max_rounds is not None else v + 1
    state = _cc_sharded_fused(
        jnp.asarray(dg.src), jnp.asarray(dg.dst),
        mesh=mesh, num_vertices=v, max_rounds=cap,
    )
    label = np.asarray(jax.device_get(state.label))
    return CcResult(
        label=label[:v],
        rounds=int(jax.device_get(state.rounds)),
        engine=f"push_sharded_x{n_shards}",
    )
