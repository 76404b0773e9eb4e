"""Batched multi-source BFS: a sources axis instead of a queue of seeds.

The oracle's multi-source BFS (BreadthFirstPaths.java:83-89,114-132) seeds
one queue with many sources and computes ``min_s dist(s, v)``.  The batched
engine here answers the stronger per-source query: independent BFS trees for
S sources in one compiled program, with the sources axis mapped to tensor
batch (and, in the sharded engine, shardable across the mesh's data axis).
``min`` over the batch axis recovers the oracle's multi-source semantics
(:func:`collapse_multi_source`).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.csr import DeviceGraph, Graph, build_device_graph, INF_DIST, NO_PARENT
from ..graph.ell import PullGraph, build_pull_graph
from ..ops.pull import relax_pull_superstep
from ..ops.relax import BfsState, init_batched_state, relax_superstep_batched
from ..analysis.runtime import traced


@functools.partial(
    jax.jit,
    static_argnames=("num_vertices", "max_levels", "packed", "telemetry"),
)
@traced("multisource._bfs_multi_fused")
def _bfs_multi_fused(
    src, dst, sources, num_vertices: int, max_levels: int,
    packed: bool = False, telemetry: bool = False,
):
    """``packed`` carries the fused ``level:6|parent:26`` word state
    (ops/packed.py) through the loop — half the per-superstep dist/parent
    HBM bytes — capped at PACKED_MAX_LEVELS and unpacked ONCE at loop
    exit, so the returned BfsState is identical wherever the cap was not
    hit (callers detect a cap exit via ``packed_truncated``).

    With ``telemetry`` (static) the loop additionally carries the
    per-level occupancy accumulator (summed over the sources axis —
    the GLOBAL curve) and returns ``(BfsState, acc)`` for one pull at
    loop exit (obs/telemetry.py)."""
    from .bfs import _loop_with_acc

    if telemetry:
        from ..obs import telemetry as T

        acc0 = T.init_level_acc(sources.shape[0], wide=True)

        def rec(a, s):
            return T.record_frontier_bools(a, s.frontier, s.level)

    if packed:
        from ..ops.packed import packed_cap
        from ..ops.relax import (
            init_packed_batched_state,
            relax_superstep_batched_packed,
            unpack_bfs_state,
        )

        cap = packed_cap(max_levels)
        pstate = init_packed_batched_state(num_vertices, sources)

        def pcond(s):
            return s.changed & (s.level < cap)

        def pbody(s):
            return relax_superstep_batched_packed(s, src, dst)

        if telemetry:
            out, acc = _loop_with_acc(pcond, pbody, pstate, acc0, rec)
            return unpack_bfs_state(out), acc
        return unpack_bfs_state(jax.lax.while_loop(pcond, pbody, pstate))
    state = init_batched_state(num_vertices, sources)

    def cond(s: BfsState):
        return s.changed & (s.level < max_levels)

    def body(s: BfsState):
        return relax_superstep_batched(s, src, dst)

    if telemetry:
        return _loop_with_acc(cond, body, state, acc0, rec)
    return jax.lax.while_loop(cond, body, state)


@functools.partial(
    jax.jit,
    static_argnames=("num_vertices", "max_levels", "packed", "telemetry"),
)
@traced("multisource._bfs_multi_pull_fused")
def _bfs_multi_pull_fused(
    ell0, folds, sources, num_vertices: int, max_levels: int,
    packed: bool = False, telemetry: bool = False,
):
    """Batched pull: the frontier table carries a leading sources axis and
    the ELL gathers broadcast over it (ops/pull.py pull_candidates), so all
    S trees advance in lock-step supersteps of one compiled loop.
    ``packed`` and ``telemetry`` as in :func:`_bfs_multi_fused`."""
    from .bfs import _loop_with_acc

    if telemetry:
        from ..obs import telemetry as T

        acc0 = T.init_level_acc(sources.shape[0], wide=True)

        def rec(a, s):
            return T.record_frontier_bools(a, s.frontier, s.level)

    if packed:
        from ..ops.packed import packed_cap
        from ..ops.pull import relax_pull_superstep_packed
        from ..ops.relax import init_packed_batched_state, unpack_bfs_state

        cap = packed_cap(max_levels)
        pstate = init_packed_batched_state(num_vertices, sources)

        def pcond(s):
            return s.changed & (s.level < cap)

        def pbody(s):
            return relax_pull_superstep_packed(s, ell0, folds)

        if telemetry:
            out, acc = _loop_with_acc(pcond, pbody, pstate, acc0, rec)
            return unpack_bfs_state(out), acc
        return unpack_bfs_state(jax.lax.while_loop(pcond, pbody, pstate))
    state = init_batched_state(num_vertices, sources)

    def cond(s: BfsState):
        return s.changed & (s.level < max_levels)

    def body(s: BfsState):
        return relax_pull_superstep(s, ell0, folds)

    if telemetry:
        return _loop_with_acc(cond, body, state, acc0, rec)
    return jax.lax.while_loop(cond, body, state)


@functools.partial(
    jax.jit,
    static_argnames=("num_vertices", "max_levels", "packed"),
    donate_argnums=(2,),
)
@traced("multisource._bfs_multi_segment")
def _bfs_multi_segment(
    src, dst, state, seg_end, num_vertices: int, max_levels: int,
    packed: bool = False,
):
    """ONE bounded segment of the batched push loop (ISSUE 14): the same
    superstep body as :func:`_bfs_multi_fused`, stopped at ``seg_end``
    supersteps (a traced operand — no retrace per segment) so the caller
    can snapshot the carry at the boundary and resume bit-identically.
    The carry is donated: a stepped segment consumes its input state
    (callers reassign), so XLA reuses the buffers instead of doubling
    the [S, V] state HBM per segment (IR001).  Unlike the fused program
    this returns the RAW carry — the once-per-run unpack happens at the
    true end (:func:`multi_segment_finish`), never at a segment
    boundary."""
    from ..ops.packed import packed_cap
    from ..ops.relax import relax_superstep_batched_packed

    cap = packed_cap(max_levels) if packed else max_levels

    def cond(s):
        return s.changed & (s.level < cap) & (s.level < seg_end)

    if packed:
        return jax.lax.while_loop(
            cond, lambda s: relax_superstep_batched_packed(s, src, dst),
            state,
        )
    return jax.lax.while_loop(
        cond, lambda s: relax_superstep_batched(s, src, dst), state
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_vertices", "max_levels", "packed"),
    donate_argnums=(2,),
)
@traced("multisource._bfs_multi_pull_segment")
def _bfs_multi_pull_segment(
    ell0, folds, state, seg_end, num_vertices: int, max_levels: int,
    packed: bool = False,
):
    """Pull-engine twin of :func:`_bfs_multi_segment` (the serve batch
    path's segment program)."""
    from ..ops.packed import packed_cap
    from ..ops.pull import relax_pull_superstep_packed

    cap = packed_cap(max_levels) if packed else max_levels

    def cond(s):
        return s.changed & (s.level < cap) & (s.level < seg_end)

    if packed:
        return jax.lax.while_loop(
            cond, lambda s: relax_pull_superstep_packed(s, ell0, folds),
            state,
        )
    return jax.lax.while_loop(
        cond, lambda s: relax_pull_superstep(s, ell0, folds), state
    )


def multi_segment_init(
    num_vertices: int, sources, packed: bool, restore: dict | None = None,
):
    """The segment loop's initial carry: a fresh batched state, or one
    rebuilt from a checkpoint epoch's host arrays (``restore`` maps state
    field names to np arrays; extra keys — checkpoint metadata — are
    ignored)."""
    from ..ops.relax import (
        PackedBfsState,
        init_packed_batched_state,
    )

    if restore is not None:
        cls = PackedBfsState if packed else BfsState
        return cls(**{
            f: jnp.asarray(restore[f]) for f in cls._fields
        })
    if packed:
        return init_packed_batched_state(
            num_vertices, jnp.asarray(np.asarray(sources, np.int32))
        )
    return init_batched_state(
        num_vertices, jnp.asarray(np.asarray(sources, np.int32))
    )


def multi_segment_finish(state, packed: bool) -> BfsState:
    """The ONCE-PER-RUN unpack at true loop exit (the fused programs do
    this inside the loop program; the segmented path defers it past the
    last segment so every intermediate snapshot stays the raw packed
    carry — V/2 state bytes per epoch)."""
    from ..ops.relax import unpack_bfs_state

    return unpack_bfs_state(state) if packed else state


@dataclass
class MultiBfsResult:
    """Per-source BFS trees: ``dist``/``parent`` are int32[S, V]."""

    sources: np.ndarray
    dist: np.ndarray
    parent: np.ndarray
    num_levels: int


def bfs_multi_device(
    graph: Graph | DeviceGraph | PullGraph,
    sources,
    *,
    engine: str = "pull",
    max_levels: int | None = None,
    block: int = 1024,
    packed: bool | None = None,
    telemetry: bool = False,
):
    """DEVICE-resident half of :func:`bfs_multi` for pull/push: returns the
    raw batched BfsState without any host transfer (``int(state.level)`` is
    the cheap sync — the benchmark timing path).  The relay analogue is
    :meth:`RelayEngine.run_multi_device`.

    ``packed=None`` runs the fused-word carry whenever parent ids fit its
    26-bit field; the loop then caps at PACKED_MAX_LEVELS and raw-device
    callers must test ``state.changed`` at the cap (:func:`bfs_multi`
    does, and falls back automatically).

    With ``telemetry`` the state comes back as ``(BfsState, acc)`` —
    the device-resident level accumulator, pulled once at loop exit
    (:func:`bfs_multi_level_curve` is the host-side convenience)."""
    sources = np.atleast_1d(np.asarray(sources, dtype=np.int32))
    from ..ops.packed import packed_parent_fits, resolve_packed
    from .bfs import check_sources

    if engine == "pull":
        pg = graph if isinstance(graph, PullGraph) else build_pull_graph(graph)
        check_sources(pg.num_vertices, sources)
        max_levels = int(max_levels) if max_levels is not None else pg.num_vertices
        if packed is None:
            packed = resolve_packed(packed_parent_fits(pg.num_vertices))
        from ..graph.ell import device_ell

        ell0_t, folds_t = device_ell(pg)
        state = _bfs_multi_pull_fused(
            ell0_t,
            folds_t,
            jnp.asarray(sources),
            pg.num_vertices,
            max_levels,
            packed,
            telemetry,
        )
        return state, pg.num_vertices
    if engine != "push":
        raise ValueError(f"unknown engine {engine!r}; use 'pull' or 'push'")
    dg = graph if isinstance(graph, DeviceGraph) else build_device_graph(graph, block=block)
    if dg.num_shards != 1:
        raise ValueError("sharded DeviceGraph requires the parallel engine")
    check_sources(dg.num_vertices, sources)
    max_levels = int(max_levels) if max_levels is not None else dg.num_vertices
    if packed is None:
        packed = resolve_packed(packed_parent_fits(dg.num_vertices))
    state = _bfs_multi_fused(
        jnp.asarray(dg.src), jnp.asarray(dg.dst), jnp.asarray(sources),
        dg.num_vertices, max_levels, packed, telemetry,
    )
    return state, dg.num_vertices


def bfs_multi(
    graph: Graph | DeviceGraph | PullGraph,
    sources,
    *,
    engine: str = "pull",
    max_levels: int | None = None,
    block: int = 1024,
) -> MultiBfsResult:
    """Batched multi-source BFS on one chip.  Engines as in
    :func:`bfs_tpu.models.bfs.bfs` — ``'pull'`` (default), ``'push'``, or
    ``'relay'`` (via :meth:`RelayEngine.run_multi`); all produce bit-exact
    dist AND parent (canonical min-parent).  Runs the packed fused-word
    carry by default and re-runs unpacked past its 62-level cap."""
    from ..ops.packed import (
        packed_parent_fits,
        packed_truncated,
        resolve_packed,
    )

    sources = np.atleast_1d(np.asarray(sources, dtype=np.int32))
    if engine == "relay":
        from .bfs import RelayEngine

        eng = graph if isinstance(graph, RelayEngine) else RelayEngine(graph)
        return eng.run_multi(sources, max_levels=max_levels)
    requested = max_levels if max_levels is not None else graph.num_vertices
    packed = resolve_packed(packed_parent_fits(graph.num_vertices))
    state, v = bfs_multi_device(
        graph, sources, engine=engine, max_levels=max_levels, block=block,
        packed=packed,
    )
    state = jax.device_get(state)
    if packed and packed_truncated(state.changed, state.level, int(requested)):
        state, v = bfs_multi_device(
            graph, sources, engine=engine, max_levels=max_levels,
            block=block, packed=False,
        )
        state = jax.device_get(state)
    return MultiBfsResult(
        sources=sources,
        dist=np.asarray(state.dist[:, :v]),
        parent=np.asarray(state.parent[:, :v]),
        num_levels=int(state.level),
    )


def bfs_multi_level_curve(
    graph: Graph | DeviceGraph | PullGraph,
    sources,
    *,
    engine: str = "pull",
    max_levels: int | None = None,
    block: int = 1024,
) -> dict:
    """The GLOBAL level curve of a batched multi-source run (occupancy
    summed over the sources axis; its total is the summed per-tree
    reachable counts).  One accumulator pull — the [S, V] dist/parent
    stay on device.  Packed runs past the 62-level cap re-run unpacked,
    same contract as :func:`bfs_multi`."""
    from ..obs.telemetry import level_curve, read_telemetry
    from ..ops.packed import (
        PACKED_MAX_LEVELS,
        packed_parent_fits,
        packed_truncated,
        resolve_packed,
    )

    sources = np.atleast_1d(np.asarray(sources, dtype=np.int32))
    requested = int(max_levels) if max_levels is not None else graph.num_vertices
    packed = resolve_packed(packed_parent_fits(graph.num_vertices))
    (state, acc), _v = bfs_multi_device(
        graph, sources, engine=engine, max_levels=max_levels, block=block,
        packed=packed, telemetry=True,
    )
    fv, changed, level = read_telemetry((acc, state.changed, state.level))
    if packed and packed_truncated(changed, level, requested):
        (state, acc), _v = bfs_multi_device(
            graph, sources, engine=engine, max_levels=max_levels,
            block=block, packed=False, telemetry=True,
        )
        fv, changed, level = read_telemetry((acc, state.changed, state.level))
        packed = False
    cap = min(PACKED_MAX_LEVELS, requested) if packed else requested
    return level_curve(fv, cap=cap)


def bfs_multi_direction(graph, sources, *, max_levels=None, config=None,
                        block: int = 1024):
    """Direction-optimizing batched multi-source BFS (ISSUE 7): the
    lock-step trees share one fused loop carrying BOTH layouts (edge
    list + ELL) and an ``lax.cond`` selects push or pull per superstep
    from the GLOBAL frontier masses (models/direction.py — the Beamer
    predicate and knobs).  Returns ``(MultiBfsResult, schedule)``,
    bit-exact with :func:`bfs_multi` under any schedule."""
    from .direction import bfs_multi_direction as _impl

    return _impl(
        graph, sources, max_levels=max_levels, config=config, block=block
    )


def collapse_multi_source(result: MultiBfsResult):
    """Reduce per-source trees to the oracle's multi-source answer:
    ``dist[v] = min_s dist_s[v]``, parent from the argmin source's tree with
    min-source tie-break (deterministic)."""
    order = np.argsort(result.sources, kind="stable")
    dist_s = result.dist[order]
    parent_s = result.parent[order]
    srcs = result.sources[order]
    best = np.argmin(dist_s, axis=0)  # first (=min source) among ties
    cols = np.arange(dist_s.shape[1])
    dist = dist_s[best, cols]
    parent = parent_s[best, cols]
    # A multi-source tree roots each source at itself (its own parent).
    is_source = np.isin(np.arange(dist.shape[0]), srcs) & (dist == 0)
    parent = np.where(is_source, np.arange(dist.shape[0]), parent)
    parent = np.where(dist == INF_DIST, NO_PARENT, parent)
    return dist.astype(np.int32), parent.astype(np.int32)
