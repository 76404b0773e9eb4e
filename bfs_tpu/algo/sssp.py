"""Weighted SSSP as min-plus supersteps with delta-stepping buckets.

The BFS superstep machinery instantiated on the ``sssp`` semiring row
(:data:`bfs_tpu.algo.substrate.SEMIRINGS`): per active edge the
contribution is ``dist[src] + w`` instead of ``src``, the combine is the
SAME segmented min (:func:`bfs_tpu.ops.relax.combine_min`), and the apply
keeps the min per destination.

**Weights.**  The weights are an operand of the program, aligned with
the dst-sorted edge arrays, and the state is typed by them: int32
distances with ``INT32_MAX`` for unreached under integer weights, float32
distances with ``+inf`` under float weights (Graph500 kernel 3), the
bucket threshold and delta in the same dtype.  A graph with stored
weights (``Graph.weight``) hands them over as they are; a graph without
fills the operand with the endpoint hash
(:func:`bfs_tpu.algo.substrate.edge_weights`, integers in
``[1, max_weight]``).  Every addition is the dtype's own: a float
distance is the float32 sum of its path's weights, left to right.

**Delta-stepping.**  The loop carry includes a bucket ``threshold`` T:
only dirty vertices with ``dist < T`` relax (the current bucket).  When
the bucket drains with dirty work remaining, T jumps to
``min(dist[dirty]) + delta`` (above ``min(dist[dirty])`` even where delta
is below half a float ulp) — the classic bucket advance, here one
``where`` on a carried scalar, no host round-trip.  ``delta=inf`` (env
``BFS_TPU_SSSP_DELTA``) degenerates to one bucket = plain frontier
Bellman-Ford; any delta yields the same fixpoint (tests pin this), it
only reshapes the superstep schedule.  Each superstep sweeps every edge,
so a search costs about supersteps x E whatever the bucket, and one
bucket takes the fewest supersteps: float weights default to it.

**One gather a superstep.**  On the TPU a per-edge gather or scatter is
a per-element loop, and it sets the superstep's cost.  The frontier
folds into the gathered distance (the identity off the frontier), and
the combine takes its scan form (:func:`bfs_tpu.ops.relax.combine_min`
with :func:`bfs_tpu.ops.relax.dst_ends`, computed once a graph), which
replaces the scatter over every edge with dense row scans and one
gather per vertex.

**Push arm.**  Most supersteps of a search touch a small share of the
edges (Bellman-Ford's long light tail), yet the sweep costs all of them.
So each unpacked one-device superstep picks its arm from what it sees:
where the frontier holds at most ``bv`` vertices with at most ``be``
out-edges in all (the smallest such tier of :data:`PUSH_TIERS`), it
expands just those edges over an out-row view of the graph
(:func:`_push_minplus`); otherwise it sweeps.  Both arms give the minima
of the same sums over the same (frontier vertex, edge) pairs, so every
carry is bit for bit the sweep's; ``push_rounds`` counts the supersteps
the push arm took.  The out-row view, ``dst_ends`` and the hash weights
are built once per graph (:func:`graph_operands`), the view only where
the push arm will read it; :func:`sssp_device` keeps them while the
caller's edge arrays live, unless the caller hands in its own.

**Parents.**  Parents are NOT carried through the loop: the
shortest-distance fixpoint determines them after the fact, in one
exit-time pass (:func:`_sssp_parents`) shared by every arm.  A vertex
takes the least ``u`` among its tight in-edges (``dist[u] + w ==
dist[v]``) with ``dist[u] < dist[v]``; where every tight in-edge ties
(a zero weight, or a float weight under half an ulp of the distance),
it takes the least tied ``u`` whose own parent is already fixed, in
rounds out from the fixed set.  Distances fall along a strict parent and
fixing rounds fall along a tied one, so the parents form a tree rooted
at the source.  Integer weights >= 1 never tie, and the rule is then
the canonical min optimal predecessor the host Dijkstra oracle applies.

**Packed arm.**  For integer weights and ``V < 2^16 - 1`` the carry word
fuses ``dist:16 | parent:16`` (the BFS ``level:6|parent:26`` word widened
for valued distances): candidates travel as packed words through ONE
uint32 ``segment_min`` and the merge is gated on STRICT distance
improvement, so the packed arm's frontier schedule and round count are
bit-identical to the unpacked arm.  Distances are clamped to 0xFFFE in
flight; a final distance hitting the clamp reports truncation and the
caller re-runs unpacked — the same detect-and-fall-back contract as the
>62-level BFS cap (``packed_truncated``).  The word's parent bits are a
provisional last-improver (diagnostic only); the exit pass is
authoritative on both arms.  Float weights never take it.

**Spans and counters.**  :func:`sssp_device` records ``sssp.search``
around ``sssp.launch`` (dispatch of the fused program), ``sssp.parents``
(dispatch of the parent pass), ``sssp.device_wait`` and ``sssp.fetch``
(``bytes``), and bumps ``sssp_searches``, ``sssp_supersteps`` and
``sssp_push_supersteps`` (the ``rounds`` and ``push_rounds`` it fetches
anyway); :func:`graph_operands` bumps ``sssp_operands_built``, and a
search that finds them kept ``sssp_operands_reused``.
"""

from __future__ import annotations

import functools
import weakref
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.runtime import traced
from ..graph.csr import Graph, NO_PARENT, build_device_graph
from ..obs.registry import get_registry
from ..obs.spans import span as obs_span
from ..ops.relax import INT32_MAX, combine_min, dst_ends
from .substrate import DEFAULT_MAX_WEIGHT, edge_weights, resolve_delta

#: Host-int mirror of the unreached sentinel for static saturation
#: arithmetic inside the supersteps (INT32_MAX itself is np.int32).
_INT32_MAX_HOST = int(INT32_MAX)

#: Packed-arm capacity: dist field holds [0, 0xFFFD]; 0xFFFE is the
#: in-flight clamp (truncation canary), 0xFFFF the unreached sentinel.
PACKED16_DIST_CLAMP = 0xFFFE
PACKED16_UNREACHED = 0xFFFF
#: Parent field capacity: ids in [0, V] with 0xFFFF = no parent, so the
#: packed arm requires V < 0xFFFF.
PACKED16_MAX_V = 0xFFFF


#: The push arm's tiers, ``(vertex budget, edge budget)`` in rising
#: order: a superstep expands its frontier's out-edges in the first tier
#: that holds both its vertex count and its out-edge count, with static
#: shapes of the tier's size.  Sized from the frontiers of a Graph500
#: s20 search (10 of 27 supersteps at most 64K edges, 3 more at most 1M
#: on at most 30K vertices).
PUSH_TIERS = ((32 * 1024, 64 * 1024), (32 * 1024, 1 << 20))
#: A tier runs only where the sweep covers at least this many times its
#: edge budget.  On a TPU v5e at Graph500 scale 20 a tier-A superstep
#: took about 17 ms, a tier-B one about 93 ms and a sweep of 2^25 edges
#: about 318 ms: about 77 ns a push slot against 9.5 ns a swept edge,
#: so a tier breaks even near 8 and this keeps it to half the sweep.
PUSH_MIN_SHRINK = 16


def push_tiers(num_slots: int, num_edges: int) -> tuple:
    """The push tiers a graph of ``num_slots`` (V+1) vertex slots and
    ``num_edges`` padded edges runs: :data:`PUSH_TIERS` with the vertex
    budget clamped to the graph, each kept where its edge budget is at
    most a :data:`PUSH_MIN_SHRINK`-th of the sweep.  Empty on a small
    graph, whose sweep is cheap."""
    tiers = []
    for bv, be in PUSH_TIERS:
        tier = (min(int(bv), int(num_slots)), int(be))
        if be * PUSH_MIN_SHRINK <= num_edges and tier not in tiers:
            tiers.append(tier)
    return tuple(tiers)


def packed16_fits(num_vertices: int) -> bool:
    """True when the dist:16|parent:16 carry can represent this graph."""
    return int(num_vertices) < PACKED16_MAX_V


def is_float(dtype) -> bool:
    """Float weights (float32 distances) rather than integer ones."""
    return np.issubdtype(dtype, np.floating)


def unreached(dtype):
    """The distance of an unreached vertex under weights of ``dtype``:
    ``+inf`` for float weights, ``INT32_MAX`` for integer ones."""
    return np.float32(np.inf) if is_float(dtype) else INT32_MAX


class SsspState(NamedTuple):
    """Unpacked loop carry.  ``dirty`` marks vertices whose dist improved
    since they last relaxed their out-edges (the delta-stepping work
    set); ``threshold`` is the current bucket's exclusive upper bound."""

    dist: jax.Array  # int32 or float32 [V+1]; unreached(dtype); slot V inert
    dirty: jax.Array  # bool[V+1]
    threshold: jax.Array  # scalar of dist's dtype
    rounds: jax.Array  # int32 scalar: supersteps executed
    changed: jax.Array  # bool scalar: dirty work remains
    push_rounds: jax.Array  # int32 scalar: supersteps the push arm took


class OutRows(NamedTuple):
    """The out-row view of the dst-sorted edges, the push arm's operand:
    row ``u`` holds ``u``'s out-edges at ``indptr[u]:indptr[u + 1]``.
    The sentinel padding edges fall in row V, never on a frontier."""

    indptr: jax.Array  # int32[V+2]
    dst: jax.Array  # int32[E]
    w: jax.Array  # [E], the weights' dtype


class PackedSsspState(NamedTuple):
    """Packed twin: ``packed`` is uint32[V+1] ``dist:16|parent:16``
    (all-ones = unreached); other fields as in :class:`SsspState`."""

    packed: jax.Array  # uint32[V+1]
    dirty: jax.Array  # bool[V+1]
    threshold: jax.Array  # int32 scalar
    rounds: jax.Array
    changed: jax.Array


def init_sssp_state(
    num_vertices: int, source, delta, dtype=jnp.int32
) -> SsspState:
    """The carry at the start of a search under weights of ``dtype``."""
    n = num_vertices + 1
    dtype = jnp.dtype(dtype)
    source = jnp.asarray(source, dtype=jnp.int32)
    dist = jnp.full((n,), unreached(dtype), dtype=dtype).at[source].set(0)
    dirty = jnp.zeros((n,), dtype=bool).at[source].set(True)
    return SsspState(
        dist, dirty, jnp.asarray(delta, dtype), jnp.int32(0), jnp.bool_(True),
        jnp.int32(0),
    )


def init_packed_sssp_state(
    num_vertices: int, source, delta: int
) -> PackedSsspState:
    n = num_vertices + 1
    source = jnp.asarray(source, dtype=jnp.int32)
    # Source word: dist 0, parent = itself.
    packed = (
        jnp.full((n,), 0xFFFFFFFF, dtype=jnp.uint32)
        .at[source]
        .set(source.astype(jnp.uint32))
    )
    dirty = jnp.zeros((n,), dtype=bool).at[source].set(True)
    return PackedSsspState(
        packed, dirty, jnp.int32(delta), jnp.int32(0), jnp.bool_(True)
    )


def packed16_dist(packed: jax.Array) -> jax.Array:
    """int32 distances from packed words (0xFFFF -> INT32_MAX)."""
    d16 = (packed >> 16).astype(jnp.int32)
    return jnp.where(d16 == PACKED16_UNREACHED, INT32_MAX, d16)


def _next_threshold(min_dirty: jax.Array, delta) -> jax.Array:
    """The next bucket's exclusive bound, ``min_dirty + delta``, always
    above ``min_dirty``.  Integers saturate (``min(.., MAX-delta)+delta``
    keeps the all-buckets threshold finite: delta=inf lands exactly on
    INT32_MAX); a float steps to the next float where delta is below half
    an ulp of ``min_dirty``, so the bucket never comes out empty."""
    if is_float(min_dirty.dtype):
        return jnp.maximum(
            min_dirty + jnp.float32(delta),
            jnp.nextafter(min_dirty, jnp.float32(jnp.inf)),
        )
    return jnp.minimum(
        min_dirty, jnp.int32(_INT32_MAX_HOST - delta)
    ) + jnp.int32(delta)


# bfs_tpu: hot traced
def sssp_superstep(
    state: SsspState,
    src: jax.Array,
    dst: jax.Array,
    w: jax.Array,
    delta,
    *,
    axis_name: str | None = None,
    ends: jax.Array | None = None,
    out: OutRows | None = None,
    tiers: tuple = (),
) -> SsspState:
    """One min-plus superstep: relax the current bucket's dirty vertices,
    then advance the bucket threshold iff it drained with work left.

    With ``tiers`` (:func:`push_tiers`) and the out-row view ``out``, on
    one device only, a frontier that fits a tier takes the push arm
    (:func:`_push_minplus`) in place of the sweep, with the same
    candidates.

    With ``axis_name``, ``src``/``dst``/``w`` are this device's edge
    shard and candidates merge across the mesh with ``lax.pmin`` — the
    identical collective shape as the BFS sharded superstep, so the
    min-plus arm inherits the replicated-state contract unchanged."""
    n = state.dist.shape[0]
    far = unreached(state.dist.dtype)
    frontier = state.dirty & (state.dist < state.threshold)

    def sweep():
        # One gather per edge (the superstep's cost on the TPU): the
        # frontier folds into the gathered distance as the identity, and
        # a lane that gathers the identity keeps it (an integer sum would
        # wrap there).
        ds = jnp.where(frontier, state.dist, far)[src]
        return combine_min(jnp.where(ds == far, far, ds + w), dst, n, ends)

    pushed = jnp.int32(0)
    if tiers:
        deg = out.indptr[1:] - out.indptr[:-1]
        arm = _push_arm(frontier.sum(dtype=jnp.int32),
                        jnp.where(frontier, deg, 0).sum(), tiers)
        cand = jax.lax.switch(arm, [
            functools.partial(_push_minplus, frontier, state.dist, deg, out,
                              bv, be)
            for bv, be in tiers
        ] + [sweep])
        pushed = (arm < len(tiers)).astype(jnp.int32)
    else:
        cand = sweep()
    if axis_name is not None:
        cand = jax.lax.pmin(cand, axis_name)
    improved = cand < state.dist
    dist = jnp.where(improved, cand, state.dist)
    dirty = (state.dirty & ~frontier) | improved
    # Bucket advance: only when the bucket drained (no frontier at all)
    # and dirty work remains beyond the threshold.
    min_dirty = jnp.min(jnp.where(dirty, dist, far))
    threshold = jnp.where(
        ~frontier.any() & (min_dirty != far),
        _next_threshold(min_dirty, delta),
        state.threshold,
    )
    return SsspState(
        dist, dirty, threshold, state.rounds + 1, dirty.any(),
        state.push_rounds + pushed,
    )


def _push_arm(num_frontier, frontier_edges, tiers: tuple) -> jax.Array:
    """The arm a superstep takes: the index of the first tier of
    ``tiers`` that holds ``num_frontier`` vertices with
    ``frontier_edges`` out-edges, else ``len(tiers)``, the sweep."""
    arm = jnp.int32(len(tiers))
    for i in reversed(range(len(tiers))):
        bv, be = tiers[i]
        fits = (num_frontier <= bv) & (frontier_edges <= be)
        arm = jnp.where(fits, jnp.int32(i), arm)
    return arm


# bfs_tpu: hot traced
def _push_minplus(frontier, dist, deg, out: OutRows, bv: int, be: int):
    """The push arm's candidates: each frontier vertex ``u`` sends
    ``dist[u] + w`` along its own out-edges, and each destination keeps
    the least (the identity where none arrives), the sweep's minima over
    the same pairs.  Static shapes: a list of ``bv`` frontier vertices
    and ``be`` edge slots, which the caller has checked hold the
    frontier.  The list comes from a cumsum of ranks and one scatter of
    ids, each slot's owner from a scatter of list indices at their
    offsets and a running max, so no step searches or sorts."""
    n = dist.shape[0]
    rank = jnp.cumsum(frontier, dtype=jnp.int32) - 1
    flist = jnp.zeros((bv,), jnp.int32).at[
        jnp.where(frontier, rank, bv)
    ].set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    listed = jnp.arange(bv, dtype=jnp.int32) <= rank[-1]
    ldeg = jnp.where(listed, deg[flist], 0)
    stop = jnp.cumsum(ldeg)
    offset = stop - ldeg
    # Each list entry's first slot maps to its first out-edge: slot j of
    # entry i reads edge ``base[i] + j``.
    base = out.indptr[flist] - offset
    marks = jnp.zeros((be,), jnp.int32).at[
        jnp.where(ldeg > 0, offset, be)
    ].max(jnp.arange(bv, dtype=jnp.int32), mode="drop")
    owner = jax.lax.cummax(marks)
    slot = jnp.arange(be, dtype=jnp.int32)
    live = slot < stop[-1]
    e = jnp.where(live, base[owner] + slot, 0)
    tgt = jnp.where(live, out.dst[e], n)
    return jnp.full((n,), unreached(dist.dtype), dist.dtype).at[tgt].min(
        dist[flist][owner] + out.w[e], mode="drop"
    )


# bfs_tpu: hot traced
def sssp_superstep_packed(
    state: PackedSsspState,
    src: jax.Array,
    dst: jax.Array,
    w: jax.Array,
    delta: int,
    *,
    axis_name: str | None = None,
    ends: jax.Array | None = None,
) -> PackedSsspState:
    """Packed twin: candidates travel as ``dist:16|parent:16`` words
    through one uint32 combine; the merge is strict on the DISTANCE field
    so the frontier schedule is bit-identical to the unpacked arm."""
    n = state.packed.shape[0]
    d16 = state.packed >> 16  # uint32; 0xFFFF = unreached
    frontier = state.dirty & (
        d16.astype(jnp.int32) < state.threshold
    ) & (d16 != PACKED16_UNREACHED)
    active = frontier[src]
    sums = jnp.minimum(
        d16[src] + w.astype(jnp.uint32), jnp.uint32(PACKED16_DIST_CLAMP)
    )
    cand_word = (sums << 16) | src.astype(jnp.uint32)
    cand = combine_min(
        jnp.where(active, cand_word, jnp.uint32(0xFFFFFFFF)), dst, n, ends
    )
    if axis_name is not None:
        cand = jax.lax.pmin(cand, axis_name)
    improved = (cand >> 16) < d16
    packed = jnp.where(improved, cand, state.packed)
    dirty = (state.dirty & ~frontier) | improved
    new_d16 = packed >> 16
    dirty_dist = jnp.where(
        dirty & (new_d16 != PACKED16_UNREACHED),
        new_d16.astype(jnp.int32),
        INT32_MAX,
    )
    min_dirty = jnp.min(dirty_dist)
    threshold = jnp.where(
        ~frontier.any() & (min_dirty != INT32_MAX),
        _next_threshold(min_dirty, delta),
        state.threshold,
    )
    return PackedSsspState(
        packed, dirty, threshold, state.rounds + 1, dirty.any()
    )


@functools.partial(jax.jit, static_argnames=("num_segments",))
@traced("algo.sssp_parents")
def _sssp_parents(dist, src, dst, w, ends, source, num_segments: int):
    """The exit-time parent pass, one rule for every arm and dtype.  A
    reached vertex takes the least ``u`` among its tight in-edges
    (``dist[u] + w == dist[v]``, in the weights' own arithmetic) with
    ``dist[u] < dist[v]``.  A vertex whose tight in-edges all tie takes,
    in rounds, the least tied ``u`` whose parent is already fixed: a
    tie round runs only where a zero-effect edge exists, never under
    integer weights >= 1.  Every reached vertex has a tight in-edge (its
    distance is some in-neighbour's plus a weight), and the tight edges
    that tie lead back to a fixed vertex, so every reached vertex gets a
    parent and the parents form a tree."""
    far = unreached(dist.dtype)
    ds = dist[src]
    dd = dist[dst]
    tight = (ds != far) & (ds + w == dd)
    parent = combine_min(
        jnp.where(tight & (ds < dd), src, INT32_MAX), dst, num_segments, ends
    )
    parent = parent.at[source].set(jnp.asarray(source, jnp.int32))
    reached = dist != far
    tied = tight & (ds == dd)

    def open_(p):
        return (reached & (p == INT32_MAX)).any()

    def fix_round(carry):
        p, _ = carry
        fixed = p != INT32_MAX
        via = combine_min(
            jnp.where(tied & fixed[src], src, INT32_MAX), dst, num_segments,
            ends,
        )
        grew = (~fixed & (via != INT32_MAX)).any()
        return jnp.where(fixed, p, via), grew

    parent, _ = jax.lax.while_loop(
        lambda c: c[1], fix_round, (parent, open_(parent))
    )
    return jnp.where(
        reached & (parent != INT32_MAX), parent, jnp.int32(NO_PARENT)
    )


@functools.partial(jax.jit, static_argnames=("max_weight",))
@traced("algo.sssp_hash_weights")
def _hash_weights(src, dst, max_weight: int):
    """The weight operand of a graph without stored weights."""
    return edge_weights(src, dst, max_weight)


@functools.partial(jax.jit, static_argnames=("num_segments",))
@traced("algo.sssp_dst_ends")
def _dst_ends(dst, num_segments: int):
    """Each destination's last edge (:func:`bfs_tpu.ops.relax.dst_ends`),
    the one per-graph operand of a graph the push arm skips."""
    return dst_ends(dst, num_segments)


@functools.partial(jax.jit, static_argnames=("num_slots",))
@traced("algo.sssp_graph_operands")
def _build_operands(src, dst, w, num_slots: int):
    """A graph's per-graph operands: each destination's last edge
    (:func:`bfs_tpu.ops.relax.dst_ends`) and the out-row view, by one
    stable sort of the dst-sorted edges by source."""
    ends = dst_ends(dst, num_slots)
    by_src, out_dst, out_w = jax.lax.sort(
        (src, dst, w), num_keys=1, is_stable=True
    )
    counts = jnp.zeros((num_slots,), jnp.int32).at[by_src].add(1)
    indptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)]
    )
    return ends, OutRows(indptr, out_dst, out_w)


class GraphOperands(NamedTuple):
    """What every search of one graph shares: the weight operand, each
    destination's last edge and, where the push arm runs, the out-row
    view (None elsewhere: every superstep sweeps)."""

    w: jax.Array
    ends: jax.Array
    out: OutRows | None

    def tiers(self) -> tuple:
        """The push tiers these operands serve (none without the view)."""
        if self.out is None:
            return ()
        return push_tiers(self.ends.shape[0], self.out.dst.shape[0])


def graph_operands(src_dev, dst_dev, num_vertices: int, *, weight=None,
                   max_weight: int = DEFAULT_MAX_WEIGHT,
                   push: bool = True) -> GraphOperands:
    """Build the per-graph operands of ``src_dev``/``dst_dev`` on the
    device: ``weight``, or the endpoint hash under ``max_weight`` where it
    is None; ``dst_ends``; and the out-row view where ``push`` (the
    unpacked carry will run) and the graph has push tiers
    (:func:`push_tiers`), the one stable sort of every edge.  A caller
    that owns residency keeps the result and hands it to
    :func:`sssp_device` (``operands=``)."""
    n = int(num_vertices) + 1
    w = weight
    if weight is None:
        w = _hash_weights(src_dev, dst_dev, max_weight=max_weight)
    get_registry().counter("sssp_operands_built")
    if push and push_tiers(n, src_dev.shape[0]):
        ends, out = _build_operands(src_dev, dst_dev, w, num_slots=n)
        return GraphOperands(w, ends, out)
    return GraphOperands(w, _dst_ends(dst_dev, num_segments=n), None)


def graph_operands_nbytes(num_vertices: int, num_edges: int, *,
                          push: bool = True) -> int:
    """Device bytes :func:`graph_operands` builds for ``num_edges``
    padded edges without stored weights: the int32 hash weights and
    ``dst_ends``, and the out-row view where it builds one."""
    n, e = int(num_vertices) + 1, int(num_edges)
    nbytes = 4 * e + 4 * n
    if push and push_tiers(n, e):
        nbytes += 4 * (n + 1) + 4 * e + 4 * e
    return nbytes


#: Operands :func:`sssp_device` built for callers that hand it none, by
#: the identities of the edge arrays they came from; an entry goes when
#: any of those arrays is collected.
_OPERANDS: dict[tuple, tuple[GraphOperands, list]] = {}


def _forget(key) -> None:
    _, finalizers = _OPERANDS.pop(key, (None, ()))
    for fin in finalizers:
        fin.detach()


def _kept_operands(src_dev, dst_dev, num_vertices: int, *, weight,
                   max_weight: int, push: bool) -> GraphOperands:
    """:func:`graph_operands`, built on a graph's first search and kept
    while ``src_dev``/``dst_dev`` (and ``weight``) live: the cache holds
    no reference to them, and a finaliser on each drops the entry when it
    is collected.  The entry's bytes belong to whoever holds those
    arrays; a caller that budgets device memory builds its own."""
    owners = (src_dev, dst_dev) if weight is None else (
        src_dev, dst_dev, weight)
    wkey = ("hash", int(max_weight)) if weight is None else id(weight)
    key = (id(src_dev), id(dst_dev), wkey, int(num_vertices), bool(push))
    hit = _OPERANDS.get(key)
    if hit is not None:
        get_registry().counter("sssp_operands_reused")
        return hit[0]._replace(w=hit[0].w if weight is None else weight)
    ops = graph_operands(src_dev, dst_dev, num_vertices, weight=weight,
                         max_weight=max_weight, push=push)
    finalizers = [weakref.finalize(a, _forget, key) for a in owners]
    # The entry keeps the hash weights it made, never the caller's.
    _OPERANDS[key] = (ops if weight is None else ops._replace(w=None),
                      finalizers)
    return ops


@functools.partial(
    jax.jit,
    static_argnames=("num_vertices", "delta", "max_rounds", "packed",
                     "tiers"),
)
@traced("algo.sssp_fused")
def _sssp_fused(
    src,
    dst,
    w,
    ends,
    out,
    source,
    num_vertices: int,
    delta,
    max_rounds: int,
    packed: bool = False,
    tiers: tuple = (),
):
    """The fused SSSP program: one ``while_loop`` of min-plus supersteps
    over the weight operand (packed or unpacked carry); ``ends``
    (:func:`bfs_tpu.ops.relax.dst_ends`) gives the combine its scan form,
    and the out-row view ``out`` the unpacked carry's push arm in the
    ``tiers`` it is given (none: every superstep sweeps)."""
    if packed:
        pstate = init_packed_sssp_state(num_vertices, source, delta)

        def pcond(s):
            return s.changed & (s.rounds < max_rounds)

        def pbody(s):
            return sssp_superstep_packed(s, src, dst, w, delta, ends=ends)

        return jax.lax.while_loop(pcond, pbody, pstate)
    state = init_sssp_state(num_vertices, source, delta, w.dtype)

    def cond(s):
        return s.changed & (s.rounds < max_rounds)

    def body(s):
        return sssp_superstep(s, src, dst, w, delta, ends=ends, out=out,
                              tiers=tiers)

    return jax.lax.while_loop(cond, body, state)


@functools.partial(
    jax.jit,
    static_argnames=("num_vertices", "delta", "packed", "tiers"),
    donate_argnums=(0,),
)
@traced("algo.sssp_segment")
def _sssp_segment(
    state,
    seg_end,
    src,
    dst,
    w,
    ends,
    out,
    num_vertices: int,
    delta,
    packed: bool = False,
    tiers: tuple = (),
):
    """ONE bounded segment of the fused loop — the checkpointable twin.
    ``seg_end`` is a TRACED round bound: advancing it costs no retrace,
    and a sequence of segments runs exactly the supersteps the fused
    program would (bit-identical carries at every boundary)."""

    def cond(s):
        return s.changed & (s.rounds < seg_end)

    if packed:

        def pbody(s):
            return sssp_superstep_packed(s, src, dst, w, delta, ends=ends)

        return jax.lax.while_loop(cond, pbody, state)

    def body(s):
        return sssp_superstep(s, src, dst, w, delta, ends=ends, out=out,
                              tiers=tiers)

    return jax.lax.while_loop(cond, body, state)


# ------------------------------------------------------------ host driver --

@dataclass
class SsspResult:
    """Host-side result in the oracle's shapes: ``dist`` (int32 with
    INT32_MAX, or float32 with +inf, for unreached) and int32[V]
    ``parent`` (sentinel slot stripped).  ``rounds`` counts executed
    supersteps including bucket-advance rounds, ``push_rounds`` those of
    them the push arm took; ``max_weight`` is the hash's range, None for
    stored weights; ``packed`` reports the carry flavor that PRODUCED the
    result (False after a truncation fallback)."""

    dist: np.ndarray
    parent: np.ndarray
    rounds: int
    max_weight: int | None
    delta: int | float
    packed: bool
    truncated_fallbacks: int = 0
    push_rounds: int = 0

    def dist_to(self, v: int):
        return self.dist[v].item()

    def has_path_to(self, v: int) -> bool:
        return bool(self.dist[v] != unreached(self.dist.dtype))


def _rounds_cap(num_vertices: int, max_rounds) -> int:
    """Supersteps a search may run.  An explicit ``max_rounds`` stops the
    loop there with whatever it holds.  The default is a true bound for
    weights >= 0, float ones included (float32 addition is monotone and
    never makes a distance smaller): a bucket drains within as many
    supersteps as it has vertices to settle, the next superstep advances
    it, and each bucket settles at least one vertex, so a search takes
    at most 2V supersteps.  A search still changing there raises
    (:func:`_converged`)."""
    if max_rounds is not None:
        return int(max_rounds)
    return 2 * (int(num_vertices) + 1)


def _converged(changed, rounds: int, max_rounds, source: int) -> None:
    """Raise where a search stopped at the default cap with work left:
    its distances would be partial."""
    if max_rounds is None and bool(changed):
        raise RuntimeError(
            f"SSSP from {source} still changing after {rounds} supersteps, "
            "the bound for weights >= 0"
        )


def _parents_and_fetch(dist_dev, counters, src_dev, dst_dev, w_dev, ends,
                       source: int, n: int):
    """Dispatch the parent pass behind the search, then bring ``dist``,
    ``parent`` and the search's device scalars ``counters`` (``rounds``,
    ``changed``, ...) to the host, each boundary under its span.  The
    host copy is queued at dispatch, so it starts when the programs
    end.  Returns ``(dist, parent, counters)``."""
    with obs_span("sssp.parents"):
        parent_dev = _sssp_parents(
            dist_dev, src_dev, dst_dev, w_dev, ends, jnp.int32(source), n
        )
    out = (dist_dev, parent_dev, *counters)
    for leaf in out:
        leaf.copy_to_host_async()
    with obs_span("sssp.device_wait"):
        out = jax.block_until_ready(out)
    nbytes = sum(int(x.nbytes) for x in out)
    with obs_span("sssp.fetch", bytes=nbytes):
        dist, parent, *counters = jax.device_get(out)
    return np.asarray(dist), np.asarray(parent), counters


def resolve_packed16(num_vertices: int) -> bool:
    """``BFS_TPU_PACKED=0/1`` forces the carry flavor (the same knob as
    BFS); otherwise packed exactly when dist:16|parent:16 fits."""
    from ..ops.packed import resolve_packed

    return resolve_packed(packed16_fits(num_vertices))


def uses_packed16(num_vertices: int, *, floating: bool,
                  packed: bool | None = None) -> bool:
    """Does a search run the packed carry?  ``packed`` where given, else
    :func:`resolve_packed16`; never over float weights."""
    return not floating and (
        resolve_packed16(num_vertices) if packed is None else bool(packed)
    )


def _device_operands(graph: Graph, block: int):
    dg = build_device_graph(graph, block=block)
    w = None if dg.weight is None else jnp.asarray(dg.weight)
    return dg, jnp.asarray(dg.src), jnp.asarray(dg.dst), w


def sssp(
    graph: Graph,
    source: int = 0,
    *,
    max_weight: int = DEFAULT_MAX_WEIGHT,
    delta: int | float | str | None = None,
    max_rounds: int | None = None,
    packed: bool | None = None,
    block: int = 1024,
) -> SsspResult:
    """Single-source shortest paths on the fused push engine.

    The graph's stored weights (``graph.weight``) when it has them, else
    ``edge_weights(src, dst, max_weight)`` — pass the same ``max_weight``
    to :func:`bfs_tpu.oracle.sssp.dijkstra` (with
    :func:`bfs_tpu.algo.substrate.edge_weights_np`) for oracle parity.
    ``packed=None`` resolves the dist:16|parent:16 arm automatically and
    falls back unpacked when a final distance hits the 16-bit clamp."""
    dg, src, dst, w = _device_operands(graph, block)
    return sssp_device(
        src, dst, dg.num_vertices, source, weight=w,
        max_weight=max_weight, delta=delta, max_rounds=max_rounds,
        packed=packed,
    )


def sssp_device(
    src_dev,
    dst_dev,
    num_vertices: int,
    source: int = 0,
    *,
    weight=None,
    max_weight: int = DEFAULT_MAX_WEIGHT,
    delta: int | float | str | None = None,
    max_rounds: int | None = None,
    packed: bool | None = None,
    operands: GraphOperands | None = None,
) -> SsspResult:
    """:func:`sssp` against ALREADY-RESIDENT sentinel-padded device edge
    arrays — the form the serve registry's residency layer feeds
    (:func:`bfs_tpu.serve.algo.registry_sssp`) and the one a caller that
    searches one graph many times keeps: operands upload once, and the
    per-graph ones (:func:`graph_operands`) are built on the first search
    and reused while the edge arrays live.  ``weight`` is the device
    weight array aligned with them (``build_device_graph(...).weight``);
    None fills it with the endpoint hash.  A caller that budgets device
    memory passes the per-graph ``operands`` it built and holds for these
    arrays, weights and carry; none are kept for it then."""
    v = int(num_vertices)
    n = v + 1
    given = weight if operands is None else operands.w
    floating = given is not None and is_float(given.dtype)
    if packed and floating:
        raise ValueError("the packed16 carry takes integer weights only")
    use_packed = uses_packed16(v, floating=floating, packed=packed)
    ops = operands
    if ops is None:
        ops = _kept_operands(src_dev, dst_dev, v, weight=weight,
                             max_weight=max_weight, push=not use_packed)
    w, ends = ops.w, ops.ends
    max_w = max_weight if weight is None else None
    delta_v = resolve_delta(delta, floating=floating)
    cap = _rounds_cap(v, max_rounds)
    if use_packed and not packed16_fits(v):
        raise ValueError(
            f"packed16 carry needs V < {PACKED16_MAX_V}, got {v}"
        )
    fallbacks = 0
    truncated = False
    push_rounds = 0
    with obs_span("sssp.search"):
        if use_packed:
            with obs_span("sssp.launch"):
                pstate = _sssp_fused(
                    src_dev, dst_dev, w, ends, ops.out, jnp.int32(source),
                    num_vertices=v, delta=delta_v, max_rounds=cap,
                    packed=True,
                )
            truncated = bool(jax.device_get(packed16_truncated(pstate.packed)))
            if truncated:
                fallbacks = 1  # clamp hit: the packed dists are not trustworthy
            else:
                dist, parent, (rounds, changed) = _parents_and_fetch(
                    packed16_dist(pstate.packed),
                    (pstate.rounds, pstate.changed),
                    src_dev, dst_dev, w, ends, source, n,
                )
        if not use_packed or truncated:
            with obs_span("sssp.launch"):
                state = _sssp_fused(
                    src_dev, dst_dev, w, ends, ops.out, jnp.int32(source),
                    num_vertices=v, delta=delta_v, max_rounds=cap,
                    packed=False, tiers=ops.tiers(),
                )
            dist, parent, (rounds, changed, push_rounds) = _parents_and_fetch(
                state.dist, (state.rounds, state.changed, state.push_rounds),
                src_dev, dst_dev, w, ends, source, n,
            )
    rounds, push_rounds = int(rounds), int(push_rounds)
    registry = get_registry()
    registry.counter("sssp_searches")
    registry.counter("sssp_supersteps", rounds)
    registry.counter("sssp_push_supersteps", push_rounds)
    _converged(changed, rounds, max_rounds, source)
    return SsspResult(
        dist=dist[:v], parent=parent[:v], rounds=rounds,
        max_weight=max_w, delta=delta_v,
        packed=use_packed and not truncated, truncated_fallbacks=fallbacks,
        push_rounds=push_rounds,
    )


@functools.partial(jax.jit)
@traced("algo.sssp_truncated")
def packed16_truncated(packed) -> jax.Array:
    """Did any final packed distance hit the in-flight clamp?  The clamp
    value doubles as the truncation canary: a genuine distance of exactly
    0xFFFE also reports truncation (conservative — the unpacked re-run is
    correct either way)."""
    return ((packed >> 16) == PACKED16_DIST_CLAMP).any()


def sssp_segmented(
    graph: Graph,
    source: int = 0,
    *,
    ckpt,
    max_weight: int = DEFAULT_MAX_WEIGHT,
    delta: int | float | str | None = None,
    max_rounds: int | None = None,
    packed: bool | None = None,
    block: int = 1024,
) -> SsspResult:
    """Checkpointed twin of :func:`sssp`: the fused loop cut into bounded
    segments with a durable epoch per boundary
    (:func:`bfs_tpu.algo.substrate.drive_segments`) — bit-identical
    results for any segmentation, kill/resume included."""
    from .substrate import drive_segments

    dg, src_dev, dst_dev, weight = _device_operands(graph, block)
    v = dg.num_vertices
    n = v + 1
    floating = weight is not None and is_float(weight.dtype)
    use_packed = uses_packed16(v, floating=floating, packed=packed)
    ops = graph_operands(src_dev, dst_dev, v, weight=weight,
                         max_weight=max_weight, push=not use_packed)
    w, ends = ops.w, ops.ends
    max_w = max_weight if weight is None else None
    delta_v = resolve_delta(delta, floating=floating)
    cap = _rounds_cap(v, max_rounds)

    def run_flavor(use_packed: bool):
        cls = PackedSsspState if use_packed else SsspState

        def init(arrays):
            if arrays is not None:
                return cls(**{
                    k: jnp.asarray(arrays[k]) for k in cls._fields
                })
            if use_packed:
                return init_packed_sssp_state(v, source, delta_v)
            return init_sssp_state(v, source, delta_v, w.dtype)

        def seg(carry, seg_end):
            return _sssp_segment(
                carry, seg_end, src_dev, dst_dev, w, ends, ops.out,
                num_vertices=v, delta=delta_v, packed=use_packed,
                tiers=() if use_packed else ops.tiers(),
            )

        return drive_segments(
            ckpt, init=init, seg=seg, fields=cls._fields,
            packed=use_packed, cap=cap,
        )

    fallbacks = 0
    if use_packed:
        pstate, rounds, changed = run_flavor(True)
        if not bool(jax.device_get(packed16_truncated(pstate.packed))):
            dist, parent, _ = _parents_and_fetch(
                packed16_dist(pstate.packed), (),
                src_dev, dst_dev, w, ends, source, n,
            )
            ckpt.clear()
            _converged(changed, rounds, max_rounds, source)
            return SsspResult(
                dist=dist[:v], parent=parent[:v], rounds=rounds,
                max_weight=max_w, delta=delta_v, packed=True,
            )
        fallbacks = 1
        ckpt.clear()  # packed epochs cannot feed the unpacked re-run
    state, rounds, changed = run_flavor(False)
    dist, parent, (push_rounds,) = _parents_and_fetch(
        state.dist, (state.push_rounds,),
        src_dev, dst_dev, w, ends, source, n,
    )
    ckpt.clear()
    _converged(changed, rounds, max_rounds, source)
    return SsspResult(
        dist=dist[:v], parent=parent[:v], rounds=rounds,
        max_weight=max_w, delta=delta_v, packed=False,
        truncated_fallbacks=fallbacks, push_rounds=int(push_rounds),
    )
