"""The semiring substrate: one superstep machine, many graph algorithms.

ROADMAP item 4's observation, made executable: nothing in the superstep
machinery is BFS-specific.  Every level-synchronous engine in this repo
is the same three-phase loop

    contribute  — per active edge, a value derived from source state;
    combine     — one segmented min over edge destinations
                  (:func:`bfs_tpu.ops.relax.combine_min`);
    apply       — merge candidates into per-vertex state, the improved
                  set becomes the next frontier, termination is
                  "nothing improved".

parameterized by a ``(contribute, combine, identity, state)`` tuple — a
commutative selection semiring, exactly the tensor-core generalization of
"Graph Traversal on Tensor Cores" (arxiv 2606.05081) and BLEST (arxiv
2512.21967).  :data:`SEMIRINGS` is the contract table (mirrored in
docs/ARCHITECTURE.md §24); the algorithm modules (:mod:`bfs_tpu.algo.sssp`,
:mod:`bfs_tpu.algo.cc`) instantiate it on the existing fused / segmented /
sharded program families.

This module also owns the two pieces the algorithms share:

  * :func:`edge_weights_np` / ``edge_weights`` — deterministic per-edge
    weights as a HASH of the endpoints, for graphs without stored
    weights.  ``w(u, v) = f(u, v)`` survives dst-sorting, sentinel
    padding and round-robin sharding with zero plumbing: any engine
    recomputes its shard's weights from the edge arrays it already holds
    (the sharded programs do it inside the ``shard_map`` body), and the
    host oracle recomputes the identical values from the host edge list.
    A graph's stored weights (``Graph.weight``, one per input edge, as
    Graph500 kernel 3 draws them) travel instead as an array that
    ``build_device_graph`` permutes and pads with the edges.
  * :func:`drive_segments` — the generic segmented-traversal driver over
    :class:`~bfs_tpu.resilience.superstep_ckpt.SuperstepCheckpointer`:
    bounded device segments, a durable epoch per boundary, the
    ``superstep:<n>`` fault family, and the shared restore gate — so
    SSSP / CC kill/resume rides the exact contract PR 14 built for BFS.
"""

from __future__ import annotations

import math
import os
import time
from dataclasses import dataclass

import numpy as np

from .. import knobs
from ..ops.relax import INT32_MAX

# --------------------------------------------------------------- contract --

@dataclass(frozen=True)
class Semiring:
    """One row of the semiring contract table (docs/ARCHITECTURE.md §24).

    ``contribute`` / ``combine`` are documentation strings — the actual
    math lives in the algorithm modules, routed through
    :func:`~bfs_tpu.ops.relax.combine_min` — plus the two capability bits
    the engine matrix branches on: ``packable`` (is there a fused-word
    carry?) and ``mxu_eligible`` (can frontier expansion run as the PR 15
    bit-packed masked matmul? only boolean-mask contributions can; valued
    contributions like min-plus sums cannot ride an AND/popcount tile).
    """

    name: str
    contribute: str
    combine: str
    identity: int
    state: tuple
    packable: bool
    mxu_eligible: bool


#: name -> contract row.  The engine matrix each algorithm ships on is
#: documented per algorithm module; this table is the shared vocabulary.
SEMIRINGS = {
    "bfs": Semiring(
        name="bfs",
        contribute="src if frontier[src]",
        combine="segment_min over dst",
        identity=int(INT32_MAX),
        state=("dist", "parent", "frontier"),
        packable=True,  # level:6|parent:26 (ops/packed.py)
        mxu_eligible=True,  # boolean masks: AND/popcount tiles (PR 15)
    ),
    # The state is typed by the weights: int32 dist (identity INT32_MAX)
    # under integer weights, hashed or stored; float32 dist (identity
    # +inf) under stored float weights (Graph500 kernel 3).
    "sssp": Semiring(
        name="sssp",
        contribute="dist[src] + w if frontier[src]; w hashed or stored",
        combine="segment_min over dst",
        identity=int(INT32_MAX),
        state=("dist", "dirty", "threshold"),
        packable=True,  # dist:16|parent:16, integer weights, V < 2^16-1
        mxu_eligible=False,  # valued contributions: no popcount encoding
    ),
    "cc": Semiring(
        name="cc",
        contribute="label[src] if frontier[src]",
        combine="segment_min over dst",
        identity=int(INT32_MAX),
        state=("label", "frontier"),
        packable=False,  # label IS the whole word already
        mxu_eligible=False,  # label values, not boolean masks
    ),
}


# ---------------------------------------------------------------- weights --
# 32-bit multiply-xorshift mix (splitmix-style finalizer constants).  The
# ONLY requirement is determinism as a pure function of (src, dst) with a
# well-spread low-bit distribution; uint32 wraparound is defined in both
# numpy array ops and XLA, so host and device values agree bit-for-bit.

_W_C1 = 0x9E3779B1
_W_C2 = 0x85EBCA77
_W_C3 = 0x7FEB352D

#: Default weight range [1, DEFAULT_MAX_WEIGHT].  255 matches the byte
#: weights of the Graph500 SSSP reference generator's integer variant.
DEFAULT_MAX_WEIGHT = 255


def edge_weights_np(src, dst, max_weight: int = DEFAULT_MAX_WEIGHT):
    """Host twin of :func:`edge_weights`: int32 weights in
    ``[1, max_weight]`` for each directed edge, bit-identical to the
    traced version (the oracle runs on these)."""
    if max_weight < 1:
        raise ValueError("max_weight must be >= 1")
    s = np.asarray(src).astype(np.uint32)
    d = np.asarray(dst).astype(np.uint32)
    h = s * np.uint32(_W_C1) + d * np.uint32(_W_C2)
    h ^= h >> np.uint32(16)
    h *= np.uint32(_W_C3)
    h ^= h >> np.uint32(15)
    return (np.uint32(1) + h % np.uint32(max_weight)).astype(np.int32)


# bfs_tpu: hot traced
def edge_weights(src, dst, max_weight: int):
    """Traced weights-from-endpoints: recomputed wherever the edge arrays
    already live (fused programs once per trace, sharded programs inside
    the mesh body) instead of shipped as a parallel operand that every
    relayout/reshard would have to permute in lockstep."""
    import jax.numpy as jnp

    s = src.astype(jnp.uint32)
    d = dst.astype(jnp.uint32)
    h = s * jnp.uint32(_W_C1) + d * jnp.uint32(_W_C2)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_W_C3)
    h = h ^ (h >> 15)
    return (jnp.uint32(1) + h % jnp.uint32(max_weight)).astype(jnp.int32)


# ------------------------------------------------------------ delta knob --

def resolve_delta(delta: int | float | str | None = None, *,
                  floating: bool = False) -> int | float:
    """The delta-stepping bucket width, in the weights' type: explicit
    argument, else ``BFS_TPU_SSSP_DELTA`` (a number, or ``inf`` for one
    bucket = plain frontier Bellman-Ford), else the default by the
    weights.  Integer weights default to 64, about half the default mean
    hash weight, the classic delta ~ w_mean starting point; float weights
    default to one bucket, since every superstep sweeps every edge and one
    bucket takes the fewest supersteps (PERF.md, Graph500 kernel 3).

    Returned as the threshold increment: for integer weights an int >= 1
    (one bucket maps to INT32_MAX: the first bucket already spans every
    finite distance, a fractional width rounds up), for float weights a
    float > 0 (one bucket is ``inf``)."""
    if delta is None:
        unset = knobs.raw("BFS_TPU_SSSP_DELTA") in (None, "")
        delta = (math.inf if floating and unset
                 else knobs.get("BFS_TPU_SSSP_DELTA"))
    if isinstance(delta, str):
        delta = knobs.parse_value("BFS_TPU_SSSP_DELTA", delta)
    delta = float(delta)
    if not delta > 0:
        delta = math.inf
    if floating:
        return delta
    return int(INT32_MAX) if delta >= INT32_MAX else math.ceil(delta)


# ------------------------------------------------------ segmented driver --

def drive_segments(ckpt, *, init, seg, fields, packed: bool, cap: int):
    """The generic segmented-traversal loop every algo engine shares.

    ``init(restore_arrays_or_None)`` builds the device carry (possibly
    resuming); ``seg(carry, seg_end)`` runs one bounded device segment;
    ``fields`` are the carry's field names (the restore gate's required
    keys); ``cap`` bounds total rounds.  The carry must expose ``rounds``
    (int32 scalar, monotone per superstep) and ``changed`` (bool scalar,
    work remains).  Returns ``(carry, rounds, changed)``.

    Epoch snapshots carry every field plus ``packed_flag`` — the same
    restore-gate contract as the BFS drivers
    (:func:`bfs_tpu.resilience.superstep_ckpt.restore_arrays`), so a
    flavor mismatch or a missing key falls back to a fresh traversal,
    never a mid-restore KeyError.  ``save_epoch`` marks the
    ``superstep:<n>`` fault boundary even with the store disabled, so
    chaos schedules target algo traversals unchanged."""
    import jax
    import jax.numpy as jnp

    from ..resilience.superstep_ckpt import restore_arrays

    arrays, _shards = restore_arrays(ckpt, packed, require=fields)
    carry = init(arrays)
    rounds, changed = jax.device_get((carry.rounds, carry.changed))
    while bool(changed) and int(rounds) < cap:
        k = ckpt.interval()
        seg_end = jnp.int32(min(int(rounds) + k, cap))
        t0 = time.perf_counter()
        carry = seg(carry, seg_end)
        new_rounds, changed = jax.device_get((carry.rounds, carry.changed))
        seg_s = time.perf_counter() - t0
        snap = {}
        if ckpt.enabled:
            snap = {
                name: np.asarray(val)
                for name, val in jax.device_get(carry)._asdict().items()
            }
            snap["packed_flag"] = np.int32(packed)
        ckpt.save_epoch(int(new_rounds), snap)
        ckpt.note_segment(int(new_rounds) - int(rounds), seg_s)
        rounds = new_rounds
    return carry, int(rounds), bool(changed)
