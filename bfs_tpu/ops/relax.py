"""The frontier-relaxation op: one BFS superstep as XLA-friendly tensor math.

This is the TPU-native replacement for the reference's map+shuffle+reduce
superstep (BfsSpark.java:66-108):

  * mapper (flatMapToPair emitting GRAY neighbours at distance+1,
    BfsSpark.java:73-79)  ->  a gather of the frontier bitmap over edge
    sources; every active edge is a candidate relaxation at ``level + 1``.
  * shuffle + reducer monoid (min-distance, argmin-path, max-color,
    BfsSpark.java:90-108)  ->  ``jax.ops.segment_min`` over edge
    destinations.  Because all candidates in a level-synchronous superstep
    share the same distance ``level + 1``, the distance reduce degenerates to
    "any active in-edge?" and the path/parent reduce to "min source id" —
    one segmented min over int32, fully VPU-vectorised, deterministic.
  * GRAY->BLACK demotion + termination substring test (BfsSpark.java:80,117)
    ->  the new frontier is exactly the improved set; termination is
    ``~improved.any()``, an on-device scalar instead of a driver-side file
    scan.

Edges must be dst-sorted with sentinel padding (csr.build_device_graph) so
``indices_are_sorted=True`` holds and padded lanes are inert.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# NumPy (not jnp) scalar: a module-level jnp constant would initialize the
# JAX backend at import time, locking the platform before callers (tests,
# dryrun) can pin CPU.  Weak-typed at trace time exactly like jnp.int32.
INT32_MAX = np.int32(2**31 - 1)


class BfsState(NamedTuple):
    """Loop carry: the device-resident replacement for the ``problemFile_i``
    files the reference writes/re-reads every superstep (BfsSpark.java:62,116).

    Shapes are ``[V+1]`` — slot V is the inert sentinel for padded edges.
    ``dist`` uses INT32_MAX for unreached (Integer.MAX_VALUE parity);
    ``parent`` is -1 for unreached, self for sources.
    """

    dist: jax.Array  # int32[V+1]
    parent: jax.Array  # int32[V+1]
    frontier: jax.Array  # bool[V+1]
    level: jax.Array  # int32 scalar: current BFS level (supersteps done)
    changed: jax.Array  # bool scalar: did the last superstep relax anything?


def init_state(num_vertices: int, source, *, sentinel: bool = True) -> BfsState:
    """Iteration-0 state (GraphFileUtil.java:50-56 parity): source at
    distance 0 on the frontier (GRAY), everything else unreached (WHITE).

    ``sentinel=False`` sizes the arrays exactly ``[V]`` — for engines whose
    candidates never index through padded edges (relay), where the ``[V+1]``
    convention would force a 4-byte-per-vertex concatenate copy every
    superstep just to append the inert slot."""
    n = num_vertices + (1 if sentinel else 0)
    source = jnp.asarray(source, dtype=jnp.int32)
    dist = jnp.full((n,), INT32_MAX, dtype=jnp.int32).at[source].set(0)
    parent = jnp.full((n,), -1, dtype=jnp.int32).at[source].set(source)
    frontier = jnp.zeros((n,), dtype=bool).at[source].set(True)
    return BfsState(dist, parent, frontier, jnp.int32(0), jnp.bool_(True))


# bfs_tpu: hot traced
def apply_candidates(
    state: BfsState,
    cand_parent: jax.Array,
    *,
    batch_axis_name: str | None = None,
) -> BfsState:
    """Merge per-vertex candidate parents into the carry: the shared tail of
    every engine's superstep (the reducer's min-merge outcome applied to
    state, BfsSpark.java:90-108).  ``cand_parent`` is INT32_MAX where no
    active in-edge exists; only unreached vertices improve (level-synchronous
    BFS discovers each vertex exactly once)."""
    improved = (cand_parent != INT32_MAX) & (state.dist == INT32_MAX)
    new_level = state.level + 1
    dist = jnp.where(improved, new_level, state.dist)
    parent = jnp.where(improved, cand_parent, state.parent)
    changed = improved.any()
    if batch_axis_name is not None:
        changed = jax.lax.pmax(changed.astype(jnp.int32), batch_axis_name) > 0
    return BfsState(dist, parent, improved, new_level, changed)


# bfs_tpu: hot traced
def relax_superstep(
    state: BfsState,
    src: jax.Array,
    dst: jax.Array,
    *,
    axis_name: str | None = None,
) -> BfsState:
    """One level-synchronous superstep.

    With ``axis_name`` set, ``src``/``dst`` are this device's edge shard and
    the candidate arrays are merged across the mesh with ``lax.pmin`` — the
    ICI all-reduce that replaces the Spark shuffle + driver collect
    (SURVEY.md §2.5).  All devices then compute identical updates, keeping
    dist/parent/frontier replicated without further collectives.
    """
    num_segments = state.dist.shape[0]
    cand_parent = _push_candidates(state.frontier, src, dst, num_segments)
    if axis_name is not None:
        cand_parent = jax.lax.pmin(cand_parent, axis_name)
    return apply_candidates(state, cand_parent)


#: Lanes of a row in the scan form of :func:`combine_min`.
SCAN_ROW = 128


def dst_ends(dst, num_segments: int) -> jax.Array:
    """The index of the last edge into each destination of a dst-sorted
    edge array, or ``len(dst)`` (out of range) for a destination with no
    in-edge: the operand of :func:`combine_min`'s scan form, fixed per
    graph, so a loop computes it once (a binary search per destination)."""
    ids = jnp.arange(num_segments, dtype=jnp.int32)
    last = jnp.searchsorted(dst, ids, side="right").astype(jnp.int32) - 1
    has = (last >= 0) & (dst[jnp.maximum(last, 0)] == ids)
    return jnp.where(has, last, jnp.int32(dst.shape[0]))


def _identity(dtype):
    """The min identity of ``dtype``: +inf, or the largest integer."""
    if jnp.issubdtype(dtype, jnp.floating):
        return np.array(np.inf, dtype)
    return np.array(np.iinfo(dtype).max, dtype)


def _shift(x, k: int, fill):
    """``x`` moved ``k`` places up its last axis, ``fill`` coming in."""
    pad = [(0, 0)] * (x.ndim - 1) + [(k, 0)]
    return jnp.pad(x[..., :-k], pad, constant_values=fill)


def _segmented_min_scan(x, d, fill):
    """Inclusive min-scan of ``x`` along its last axis, restarting where
    the sorted keys ``d`` change (Hillis-Steele doubling: equal keys ``k``
    apart bound a run of equal keys)."""
    k = 1
    while k < x.shape[-1]:
        same = _shift(d, k, -1) == d
        x = jnp.where(same, jnp.minimum(x, _shift(x, k, fill)), x)
        k *= 2
    return x


# bfs_tpu: hot traced
def combine_min(values, dst, num_segments: int, ends=None) -> jax.Array:
    """THE semiring combine: one segmented min of per-edge contribution
    values over edge destinations (identity = the dtype's max sentinel).

    Every algorithm on the superstep machinery reduces through this one
    op — BFS contributes ``src`` ids (min-id parent), SSSP contributes
    ``dist[src] + w`` min-plus sums, connected components contributes
    ``label[src]`` (bfs_tpu/algo/substrate.py's semiring table).  Edges
    must be dst-sorted with sentinel padding (csr.build_device_graph) so
    ``indices_are_sorted=True`` holds and padded lanes are inert.

    With ``ends`` (:func:`dst_ends` of the same ``dst``) the same minima
    come from a scan in place of the scatter: a segmented min-scan along
    rows of up to :data:`SCAN_ROW` edges, the rows' tails scanned by
    their keys and carried into the next row's first run, then one
    gather of each destination's last edge.  On the TPU the scatter
    serialises over every edge; the scan is dense vector work plus one
    gather per destination."""
    if ends is None:
        return jax.ops.segment_min(
            values, dst, num_segments=num_segments, indices_are_sorted=True
        )
    fill = _identity(values.dtype)
    e = values.shape[0]
    width = min(SCAN_ROW, e & -e)
    d = dst.reshape(-1, width)
    x = _segmented_min_scan(values.reshape(-1, width), d, fill)
    tails = _segmented_min_scan(x[:, -1], d[:, -1], fill)
    carry = jnp.where(_shift(d[:, -1], 1, -1) == d[:, 0],
                      _shift(tails, 1, fill), fill)
    x = jnp.where(d == d[:, :1], jnp.minimum(x, carry[:, None]), x)
    return x.reshape(-1).at[ends].get(mode="fill", fill_value=fill)


def _push_candidates(frontier, src, dst, num_segments: int) -> jax.Array:
    """Min source id among active in-edges per destination; INT32_MAX where
    none (the mapper + reducer monoid as one segmented min) — BFS's
    instance of :func:`combine_min`."""
    active = frontier[src]
    return combine_min(
        jnp.where(active, src, INT32_MAX), dst, num_segments
    )


# ----------------------------------------------------------- packed state --
# The ``level:6 | parent:26`` fused-word carry (ops/packed.py): dist and
# parent collapse into one uint32 per vertex, halving the per-superstep
# state-update HBM bytes, and the improvement test + canonical min-parent
# tie-break collapse into one unsigned ``min``.  Engines run this by
# default (V permitting) and fall back to BfsState past PACKED_MAX_LEVELS.


class PackedBfsState(NamedTuple):
    """Packed loop carry: ``packed`` is uint32[V+1] (``level:6|parent:26``,
    all-ones unreached — ops/packed.py); other fields as in BfsState."""

    packed: jax.Array  # uint32[V+1]
    frontier: jax.Array  # bool[V+1]
    level: jax.Array
    changed: jax.Array


def init_packed_state(
    num_vertices: int, source, *, sentinel: bool = True
) -> PackedBfsState:
    """Packed twin of :func:`init_state`: source at level 0 with itself as
    parent (word ``0<<26 | source``), everything else the sentinel."""
    from .packed import PACKED_SENTINEL

    n = num_vertices + (1 if sentinel else 0)
    source = jnp.asarray(source, dtype=jnp.int32)
    packed = (
        jnp.full((n,), PACKED_SENTINEL, dtype=jnp.uint32)
        .at[source]
        .set(source.astype(jnp.uint32))
    )
    frontier = jnp.zeros((n,), dtype=bool).at[source].set(True)
    return PackedBfsState(packed, frontier, jnp.int32(0), jnp.bool_(True))


def init_packed_batched_state(num_vertices: int, sources) -> PackedBfsState:
    """Packed twin of :func:`init_batched_state` ([S, V+1] fields)."""
    from .packed import PACKED_SENTINEL

    n = num_vertices + 1
    sources = jnp.asarray(sources, dtype=jnp.int32)
    s = sources.shape[0]
    rows = jnp.arange(s)
    packed = (
        jnp.full((s, n), PACKED_SENTINEL, dtype=jnp.uint32)
        .at[rows, sources]
        .set(sources.astype(jnp.uint32))
    )
    frontier = jnp.zeros((s, n), dtype=bool).at[rows, sources].set(True)
    return PackedBfsState(packed, frontier, jnp.int32(0), jnp.bool_(True))


# bfs_tpu: hot traced
def apply_candidates_packed(
    state: PackedBfsState,
    cand_parent: jax.Array,
    *,
    batch_axis_name: str | None = None,
) -> PackedBfsState:
    """Packed tail of the push/pull supersteps: the candidate parent ids
    (int32, INT32_MAX where none) become packed words at ``level+1`` and
    merge with ONE lexicographic min — half the dist/parent HBM bytes of
    :func:`apply_candidates`, same canonical tie-break."""
    from .packed import PACKED_SENTINEL, level_word, merge_packed

    lev = level_word(state.level + 1)
    cand = jnp.where(
        cand_parent == INT32_MAX,
        jnp.uint32(PACKED_SENTINEL),
        cand_parent.astype(jnp.uint32) | lev,
    )
    packed = merge_packed(state.packed, cand)
    improved = packed != state.packed
    changed = improved.any()
    if batch_axis_name is not None:
        changed = jax.lax.pmax(changed.astype(jnp.int32), batch_axis_name) > 0
    return PackedBfsState(packed, improved, state.level + 1, changed)


# bfs_tpu: hot traced
def relax_superstep_packed(
    state: PackedBfsState,
    src: jax.Array,
    dst: jax.Array,
    *,
    axis_name: str | None = None,
) -> PackedBfsState:
    """Packed twin of :func:`relax_superstep` (same candidates, min-merge
    state update)."""
    num_segments = state.packed.shape[0]
    cand_parent = _push_candidates(state.frontier, src, dst, num_segments)
    if axis_name is not None:
        cand_parent = jax.lax.pmin(cand_parent, axis_name)
    return apply_candidates_packed(state, cand_parent)


# bfs_tpu: hot traced
def _batched_push_candidates(frontier, src, dst, num_segments: int):
    """Edge-major batched candidates: gather the frontier per EDGE
    (``frontier.T[src]`` -> (E, S)) and run ONE segment_min over the
    leading edge axis, transposing back at the end.  The vmap-over-rows
    spelling computed the same values but made XLA:CPU materialize a
    layout-changing (E, S) copy of the whole candidate buffer inside the
    while body every superstep (HLO003's first dogfood catch — E*S*4
    bytes/superstep of copy traffic); edge-major keeps the gather, the
    where and the scatter-min in one natural layout and the closing
    transpose fuses into the elementwise consumer."""
    active = frontier.T[src]  # (E, S)
    cand = jnp.where(active, src[:, None], INT32_MAX)
    return jax.ops.segment_min(
        cand, dst, num_segments=num_segments, indices_are_sorted=True
    ).T


# bfs_tpu: hot traced
def relax_superstep_batched_packed(
    state: PackedBfsState,
    src: jax.Array,
    dst: jax.Array,
    *,
    axis_name: str | None = None,
    batch_axis_name: str | None = None,
) -> PackedBfsState:
    """Packed twin of :func:`relax_superstep_batched`."""
    cand_parent = _batched_push_candidates(
        state.frontier, src, dst, state.packed.shape[-1]
    )
    if axis_name is not None:
        cand_parent = jax.lax.pmin(cand_parent, axis_name)
    return apply_candidates_packed(
        state, cand_parent, batch_axis_name=batch_axis_name
    )


def unpack_bfs_state(state: PackedBfsState) -> BfsState:
    """The ONCE-PER-RUN unpack at fused-loop exit (on device): packed words
    back to the int32 dist/parent contract every downstream consumer
    (oracle check, wire format, serve replies) already speaks."""
    from .packed import packed_dist, packed_parent

    return BfsState(
        dist=packed_dist(state.packed),
        parent=packed_parent(state.packed),
        frontier=state.frontier,
        level=state.level,
        changed=state.changed,
    )


def init_batched_state(
    num_vertices: int, sources: jax.Array, *, sentinel: bool = True
) -> BfsState:
    """Batched multi-source state: fields carry a leading sources axis
    ``[S, V+1]`` while ``level``/``changed`` stay scalar (all sources advance
    in lock-step supersteps).  The oracle's multi-source ctor seeds all
    sources at distance 0 (BreadthFirstPaths.java:114-132); batching them as
    a tensor axis instead is the vmap analogue (BASELINE.json config 5).
    ``sentinel`` as in :func:`init_state`."""
    n = num_vertices + (1 if sentinel else 0)
    sources = jnp.asarray(sources, dtype=jnp.int32)
    s = sources.shape[0]
    rows = jnp.arange(s)
    dist = jnp.full((s, n), INT32_MAX, dtype=jnp.int32).at[rows, sources].set(0)
    parent = jnp.full((s, n), -1, dtype=jnp.int32).at[rows, sources].set(sources)
    frontier = jnp.zeros((s, n), dtype=bool).at[rows, sources].set(True)
    return BfsState(dist, parent, frontier, jnp.int32(0), jnp.bool_(True))


# bfs_tpu: hot traced
def relax_superstep_batched(
    state: BfsState,
    src: jax.Array,
    dst: jax.Array,
    *,
    axis_name: str | None = None,
    batch_axis_name: str | None = None,
) -> BfsState:
    """Batched superstep over a leading sources axis.

    ``axis_name`` merges edge shards with ``pmin`` (graph/"context" axis);
    ``batch_axis_name`` reduces the termination flag across a sharded sources
    axis (data-parallel axis) so every device agrees on loop exit.
    """
    cand_parent = _batched_push_candidates(
        state.frontier, src, dst, state.dist.shape[-1]
    )
    if axis_name is not None:
        cand_parent = jax.lax.pmin(cand_parent, axis_name)
    return apply_candidates(state, cand_parent, batch_axis_name=batch_axis_name)


def frontier_size(state: BfsState) -> jax.Array:
    """Number of GRAY vertices — the per-superstep metric the reference can
    only obtain by scanning the serialized file (BfsSpark.java:117)."""
    return state.frontier.sum(dtype=jnp.int32)
