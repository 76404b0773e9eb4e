"""Relay layout v4: degree-class dense adjacency + Beneš-routed bit shuffle.

The fully gather-free BFS data layout.  Measured reality on TPU v5e
(tools/microbench_gather.py): dense vector ops run at memory bandwidth while
every XLA gather/scatter runs at ~0.1 G/s, so the engine may not index by
edge at runtime AT ALL.  Everything data-dependent becomes dense math over
static layouts:

  * **src side (broadcast)** — vertices bucketed by OUT-degree class; a
    vertex's frontier bit is broadcast to its out-edge slots (the mapper
    emitting a candidate per neighbour, BfsSpark.java:73-79, as pure word
    replication).
  * **the shuffle** — per-edge bits move from src-grouped to dst-grouped
    slot order through a bit-packed Beneš network (2*log2 N - 1 dense
    butterfly stages, masks precomputed by native/benes.cpp).  This is the
    reference's `reduceByKey` shuffle (BfsSpark.java:90) compiled into a
    routing circuit.
  * **dst side (reduce)** — vertices bucketed by IN-degree class and
    RELABELED so classes are contiguous; the reducer's min-merge becomes a
    min-active-slot scan per class.  Within a dst row slots ascend by
    ORIGINAL src id, so min slot == canonical min-parent.

v4 changes vs the round-2 layout (LAYOUT_VERSION 3):

  * **Standard (word-major) packing everywhere**: element ``e`` lives at
    (word ``e >> 5``, bit ``e & 31``).  This is what the native router
    emits, so the router's bit-major transpose pass is gone; classes are
    32-aligned so the broadcast becomes pure word replication and the
    row-min a word-level scan — the round-2 pack/unpack kernels disappear.
  * **Pair-compacted masks**: a stage with element distance d only has
    switch bits at the lower index of each pair ((e & d) == 0), so for
    d >= 32*128 the mask rows at (row & (d/4096)) != 0 are structurally
    zero; they are dropped from storage, cutting streamed mask bytes ~29%
    (tools/mask_sparsity.py measurement round 3).
  * **Identity tail**: pad slots beyond max(m1, m2) are wired
    input==output and each stage stores its nonzero word range so kernels
    can skip dead blocks.  NOTE: pads route switch-free only where BOTH
    members of a top-stage pair are pads (live <= n/2); at the bench's
    m1 ~ 0.94n the ranges rarely shrink — the real mask-byte win is the
    pair compaction above.
"""

from __future__ import annotations

import logging
import time as _time
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

logger = logging.getLogger(__name__)

# BFS_TPU_BUILD_LOG=1 turns on the per-phase build timing logs without the
# caller configuring logging (a bare handler at INFO on this module only).
# Checked lazily at each build so callers that set the flag after this
# module is first imported (e.g. a process that imports relay early and
# decides on logging later, as bench.main does) still get the stamps.
# Reversible: setting BFS_TPU_BUILD_LOG=0 (or unsetting it) before the next
# build removes the handler and resets the level, and the install/remove is
# lock-guarded so concurrent first builds cannot double-install the handler.
_build_log_lock = __import__("threading").Lock()
_build_log_handler: logging.Handler | None = None
_build_log_prev_level: int | None = None


def _ensure_build_log():
    global _build_log_handler, _build_log_prev_level
    from .. import knobs

    enabled = knobs.get("BFS_TPU_BUILD_LOG")
    with _build_log_lock:
        if enabled:
            if _build_log_handler is None:
                _h = logging.StreamHandler()
                _h.setFormatter(logging.Formatter("%(asctime)s %(message)s"))
                logger.addHandler(_h)
                _build_log_handler = _h
                _build_log_prev_level = logger.level
            logger.setLevel(logging.INFO)
        elif _build_log_handler is not None:
            # Only undo what this latch installed: remove OUR handler and
            # restore the level the logger had before we raised it, so an
            # application-configured handler/level is left untouched.
            logger.removeHandler(_build_log_handler)
            _build_log_handler = None
            logger.setLevel(_build_log_prev_level)
            _build_log_prev_level = None


_ensure_build_log()


class _phase:
    """Build-phase timer: logs at INFO (enable with BFS_TPU_BUILD_LOG=1 or
    logging config) so the <300 s layout-build budget stays accountable."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = _time.perf_counter()
        return self

    def __exit__(self, *exc):
        logger.info(
            "layout phase %-22s %.1fs", self.name,
            _time.perf_counter() - self.t0,
        )

from . import benes
from .csr import DeviceGraph, Graph, INF_DIST

#: Bump when the slot ordering / mask layout changes; layout caches
#: (bench.py .bench_cache) key on it.
LAYOUT_VERSION = 4

#: Stages with element distance >= COMPACT_MIN_D store only the words at the
#: lower index of each word pair (see StageSpec.compact).  d >= 4096 makes
#: the word distance >= 128 (a whole 128-lane row), so the compact view is a
#: contiguous row slice — clean for both XLA reshapes and Pallas DMA.
COMPACT_MIN_D = 4096


class StageSpec(NamedTuple):
    """Static per-stage metadata for a stored Beneš network.

    ``d``: element distance of the butterfly.
    ``offset``: word offset of this stage's mask data in the flat array.
    ``nwords``: stored words (n/32 full, n/64 compact).
    ``compact``: pair-compacted storage (only words at (w & d>>5) == 0).
    ``lo``/``hi``: [lo, hi) nonzero word range within the stored words —
    kernels skip blocks outside it (identity-wired tail routes switch-free).
    """

    d: int
    offset: int
    nwords: int
    compact: bool
    lo: int
    hi: int


@dataclass(frozen=True)
class ClassSlice:
    """One degree class: vertices/positions [va, vb) own slots [sa, sb).

    ``count`` is the PADDED count (multiple of 32 for rank-major classes);
    ``real`` the real vertex count; ``width`` the padded slot width.
    Slot layout: rank-major ``slot = sa + r*count + p`` (a word is 32
    consecutive p at one rank — broadcast replicates whole words, row-min
    scans words at stride count/32); vertex-major ``slot = sa + p*width + r``
    (width % 32 == 0 — a row is width/32 consecutive words), used for the few
    huge-width classes where rank-major padding would explode.
    """

    width: int
    va: int
    vb: int  # va + count
    sa: int
    sb: int
    real: int
    vertex_major: bool = False
    real_width: int = -1  # pre-padding width (== width for rank-major)

    @property
    def count(self) -> int:
        return self.vb - self.va


def _class_width(deg: np.ndarray) -> np.ndarray:
    """Degree-class width: degree rounded up to {2^k, 3*2^(k-1)} — one
    mantissa bit instead of pure powers of two.  Worst-case padding stays
    just under 50% vs 100% for pow2; on the scale-24 R-MAT this keeps the
    slot count ~1.25E, which decides whether the Beneš network fits the next
    power of two."""
    x = np.maximum(np.asarray(deg, dtype=np.int64), 1)
    p2 = np.int64(1) << np.int64(
        np.ceil(np.log2(x.astype(np.float64)))
    ).astype(np.int64)
    p2 = np.maximum(p2, 1)
    three_quarter = (p2 // 4) * 3
    return np.where((p2 >= 4) & (x <= three_quarter), three_quarter, p2)


# --------------------------------------------------------------------------
# Shared classing helpers.  `_class_width` above is the closed-form rule;
# the helpers below are the TABLE form of the same math — a static ascending
# candidate list plus searchsorted — which (a) is exact integer arithmetic
# (no float log2), so the device builder (graph/relay_device.py) can run it
# under jax's default 32-bit floats, and (b) turns the per-class Python
# loops of the builders into single vectorized passes (the sharded
# builder's per-shard classing below reuses them host-side).
# --------------------------------------------------------------------------

def width_candidates(max_width: int = 1 << 31) -> np.ndarray:
    """Every value `_class_width` can produce, ascending: {2^k, 3*2^(k-1)}.
    ``width = candidates[searchsorted(candidates, degree)]`` — the smallest
    candidate >= degree — is exactly `_class_width(degree)`."""
    out = [1, 2]
    k = 2
    while (1 << k) <= max_width:
        out.append(3 << (k - 2))  # 3*2^(k-1) for the next power of two
        out.append(1 << k)
        k += 1
    return np.array([c for c in out if c <= max_width], dtype=np.int64)


def width_index(deg: np.ndarray, candidates: np.ndarray) -> np.ndarray:
    """Index into ``candidates`` of `_class_width(deg)` (exact, integer)."""
    x = np.maximum(np.asarray(deg, dtype=np.int64), 1)
    return np.searchsorted(candidates, x, side="left").astype(np.int32)


def ranked_placement(
    group: np.ndarray, base_by_group: np.ndarray
) -> np.ndarray:
    """``pos[i] = base_by_group[group[i]] + rank``, where rank is item
    ``i``'s stable rank within its group ordered by (group, original
    index).  The vectorized form of the builders' per-class placement
    loops (one `_sort_rank` pass instead of a Python loop over classes)."""
    n = int(np.asarray(group).shape[0])
    order, rank = _sort_rank(
        np.asarray(group, dtype=np.int32), np.arange(n, dtype=np.int32)
    )
    out = np.empty(n, dtype=np.int64)
    out[order] = base_by_group[np.asarray(group)[order]] + rank
    return out


def _pow2_at_least(n: int) -> int:
    n = max(int(n), 32)
    return 1 << (n - 1).bit_length()


def _round32(x: int) -> int:
    return (int(x) + 31) & ~31


def _build_classes(widths: np.ndarray, counts: np.ndarray) -> list[ClassSlice]:
    """Aligned class slices from per-width real counts (widths ascending).

    Vertex-major iff width >= max(count, 32) (few huge-width vertices: pad
    the width to a multiple of 32); otherwise rank-major (pad the count).
    Rank-major classes come first so the padded vertex ranges stay 32-aligned
    even when vertex-major classes have unpadded counts.
    """
    order = np.argsort(widths, kind="stable")
    rank_major = [
        (int(widths[i]), int(counts[i]))
        for i in order
        if not widths[i] >= max(counts[i], 32)
    ]
    vertex_major = [
        (int(widths[i]), int(counts[i]))
        for i in order
        if widths[i] >= max(counts[i], 32)
    ]
    slices: list[ClassSlice] = []
    va = 0
    sa = 0
    for w, c in rank_major:
        cp = _round32(c)
        slices.append(
            ClassSlice(width=w, va=va, vb=va + cp, sa=sa, sb=sa + w * cp,
                       real=c, vertex_major=False, real_width=w)
        )
        va += cp
        sa += w * cp
    for w, c in vertex_major:
        wp = _round32(w)
        slices.append(
            ClassSlice(width=wp, va=va, vb=va + c, sa=sa, sb=sa + wp * c,
                       real=c, vertex_major=True, real_width=w)
        )
        va += c
        sa += wp * c
    return slices


def _gather(table: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """int32 gather with the native fast path (numpy fancy indexing is the
    layout build's second-biggest cost after routing on the 1-core VM)."""
    try:
        from .native_gen import gather_i32_native, native_available

        if native_available() and table.dtype == np.int32:
            return gather_i32_native(table, idx)
    except Exception:
        pass
    return table[idx]


def _scatter(out: np.ndarray, idx: np.ndarray, val: np.ndarray) -> None:
    try:
        from .native_gen import native_available, scatter_i32_native

        if native_available() and out.dtype == np.int32:
            scatter_i32_native(out, idx, val)
            return
    except Exception:
        pass
    out[idx] = val


def _slot_assign(base, stride, idx, rank) -> np.ndarray:
    try:
        from .native_gen import native_available, slot_assign_native

        if native_available():
            return slot_assign_native(base, stride, idx, rank)
    except Exception:
        pass
    return base[idx] + rank * stride[idx]


def _rank_by_count(key: np.ndarray, nk: int) -> np.ndarray:
    """Arbitrary-but-stable rank within each key group (native one-pass; a
    cumcount fallback otherwise)."""
    try:
        from .native_gen import native_available, rank_by_count_native

        if native_available():
            return rank_by_count_native(key, nk)
    except Exception:
        pass
    order = np.argsort(key, kind="stable")
    ks = key[order]
    starts = np.flatnonzero(np.concatenate([[True], ks[1:] != ks[:-1]]))
    sor = starts[np.searchsorted(starts, np.arange(ks.shape[0]), side="right") - 1]
    rank = np.empty_like(order)
    rank[order] = (np.arange(ks.shape[0]) - sor).astype(np.int32)
    return rank.astype(np.int32)


def _mark_used(idx: np.ndarray, used: np.ndarray) -> None:
    """used[idx] = 1 on a uint8 array (native scatter fast path)."""
    try:
        from .native_gen import mark_u8_native, native_available

        if native_available():
            mark_u8_native(idx, used)
            return
    except Exception:
        pass
    used[np.asarray(idx)] = 1


def _csr_fill(srcn, dstn, slotv, nk: int):
    """Counting-sort CSR grouped by ``srcn`` (arbitrary within-row order);
    returns (indptr int32[nk+2], adj_dst, adj_slot)."""
    try:
        from .native_gen import csr_fill_native, native_available

        if native_available():
            return csr_fill_native(srcn, dstn, slotv, nk)
    except Exception:
        pass
    order = np.argsort(srcn, kind="stable")
    indptr = np.zeros(nk + 2, dtype=np.int64)
    np.cumsum(np.bincount(srcn, minlength=nk), out=indptr[1 : nk + 1])
    indptr[nk + 1] = indptr[nk]
    return (
        indptr.astype(np.int32),
        np.asarray(dstn)[order].astype(np.int32),
        np.asarray(slotv)[order].astype(np.int32),
    )


def _sort_rank(key_hi: np.ndarray, key_lo: np.ndarray):
    """(order, rank-within-hi-runs) sorted by (key_hi, key_lo) — native radix
    when available, np.lexsort fallback."""
    try:
        from .native_gen import native_available, sort_rank_pairs_native

        if native_available():
            return sort_rank_pairs_native(key_hi, key_lo)
    except Exception:
        pass
    order = np.lexsort((key_lo, key_hi))
    hs = np.asarray(key_hi)[order]
    n = hs.shape[0]
    if n == 0:
        return order.astype(np.int32), np.zeros(0, np.int32)
    starts = np.flatnonzero(np.concatenate([[True], hs[1:] != hs[:-1]]))
    sor = starts[np.searchsorted(starts, np.arange(n), side="right") - 1]
    return order.astype(np.int32), (np.arange(n) - sor).astype(np.int32)


def _vertex_tables(classes: list[ClassSlice], num_ids: int):
    """Per-(relabeled id / out-position) slot tables: slot(id, r) =
    base[id] + r * stride[id].  Rank-major: base = sa + p, stride = count;
    vertex-major: base = sa + p*width, stride = 1."""
    base = np.zeros(num_ids, dtype=np.int32)
    stride = np.ones(num_ids, dtype=np.int32)
    for cs in classes:
        p = np.arange(cs.count, dtype=np.int32)
        if cs.vertex_major:
            base[cs.va : cs.vb] = cs.sa + p * cs.width
            stride[cs.va : cs.vb] = 1
        else:
            base[cs.va : cs.vb] = cs.sa + p
            stride[cs.va : cs.vb] = cs.count
    return base, stride


def _compact_and_table(
    masks: np.ndarray, n: int
) -> tuple[np.ndarray, tuple[StageSpec, ...]]:
    """Pair-compact the router's word-major masks and build the stage table.

    For each stage with d >= COMPACT_MIN_D, keep only the word rows at
    (row & (d >> 12)) == 0 (the rest are structurally zero: switch bits live
    at the lower pair index).  Also records each stage's nonzero word range
    so appliers can skip the identity-wired tail."""
    nw = n // 32
    stages = masks.shape[0]
    parts = []
    table = []
    offset = 0
    for s in range(stages):
        d = benes.stage_distance(n, s)
        w = masks[s]
        if d >= COMPACT_MIN_D:
            dw = d >> 5
            w = w.reshape(-1, 2, dw)[:, 0, :].reshape(-1)
        nz = np.flatnonzero(
            w.reshape(-1, 1024).any(axis=1)
            if w.shape[0] % 1024 == 0
            else w
        )
        if w.shape[0] % 1024 == 0:
            lo = int(nz[0]) * 1024 if nz.size else 0
            hi = int(nz[-1] + 1) * 1024 if nz.size else 0
        else:
            lo = int(nz[0]) if nz.size else 0
            hi = int(nz[-1] + 1) if nz.size else 0
        parts.append(w)
        table.append(
            StageSpec(d=d, offset=offset, nwords=int(w.shape[0]),
                      compact=d >= COMPACT_MIN_D, lo=lo, hi=hi)
        )
        offset += int(w.shape[0])
    return np.concatenate(parts), tuple(table)


@dataclass(frozen=True)
class RelayGraph:
    """Static relay layout v4 for one graph (single shard).

    All vertex-indexed engine state lives in the RELABELED id space of size
    ``vr`` (``new2old``/``old2new``; -1 at padding dummies); parent VALUES
    are L1 slot indices mapped to original src ids host-side via ``src_l1``.
    """

    num_vertices: int  # real V
    num_edges: int
    vr: int  # padded relabeled vertex space (multiple of 32)
    new2old: np.ndarray  # int32[vr]; -1 at dummies
    old2new: np.ndarray  # int32[V]
    # src side
    vperm_masks: np.ndarray  # uint32 flat
    vperm_table: tuple[StageSpec, ...]
    vperm_size: int
    out_classes: tuple[ClassSlice, ...]  # over out-order positions
    out_space: int  # used out positions (sum of class counts)
    # shuffle
    net_masks: np.ndarray  # uint32 flat
    net_table: tuple[StageSpec, ...]
    net_size: int
    m1: int
    m2: int
    # dst side
    in_classes: tuple[ClassSlice, ...]  # over relabeled vertex space
    src_l1: np.ndarray  # int32[m1] — ORIGINAL src id per L1 slot, INF padding
    # sparse-path adjacency: CSR over RELABELED src ids with, per out-edge,
    # the relabeled dst and that edge's L1 slot.  The hybrid engine gathers
    # these for small frontiers instead of paying the full-net superstep
    # (supersteps 0 and the >=3 tail carry <2% of the edges at scale 24 —
    # tools/measure_r3.py level profile).
    adj_indptr: np.ndarray  # int32[vr + 2] (last entry repeated)
    adj_dst: np.ndarray  # int32[E]
    adj_slot: np.ndarray  # int32[E]


def extract_edges(graph: Graph | DeviceGraph):
    """Host edge extraction shared by both builders: ``(src, dst, v, e)``."""
    if isinstance(graph, DeviceGraph):
        if graph.num_shards != 1:
            raise ValueError("build_relay_graph expects a single-shard graph")
        flat_src = np.asarray(graph.src).reshape(-1)
        flat_dst = np.asarray(graph.dst).reshape(-1)
        keep = flat_dst != graph.sentinel
        src = flat_src[keep].astype(np.int32)
        dst = flat_dst[keep].astype(np.int32)
        v = graph.num_vertices
    else:
        src = np.asarray(graph.src).astype(np.int32)
        dst = np.asarray(graph.dst).astype(np.int32)
        v = graph.num_vertices
    return src, dst, int(v), int(src.shape[0])


def seg_degrees(src: np.ndarray, dst: np.ndarray, v: int):
    """Per-vertex degree-class widths (zero-indeg vertices get one INF
    slot) — native bincount fast path."""
    try:
        from .native_gen import bincount_i32_native, native_available

        if native_available():
            indeg = bincount_i32_native(dst, v).astype(np.int64)
            outdeg = bincount_i32_native(src, v).astype(np.int64)
        else:
            raise RuntimeError
    except Exception:
        indeg = np.bincount(dst, minlength=v)
        outdeg = np.bincount(src, minlength=v)
    return _class_width(indeg), _class_width(outdeg)


class LayoutMeta(NamedTuple):
    """Static layout metadata derived from the two degree histograms — the
    shapes every later segment (and the device builder's programs) key on."""

    in_classes: tuple
    out_classes: tuple
    widths: np.ndarray
    counts: np.ndarray
    owidths: np.ndarray
    ocounts: np.ndarray
    vr: int
    m1: int
    m2: int
    out_vb: int
    n: int
    vp: int


def seg_classes_from_counts(
    widths: np.ndarray, counts: np.ndarray,
    owidths: np.ndarray, ocounts: np.ndarray, v: int,
) -> LayoutMeta:
    """Aligned classes + every derived static size from per-width counts.
    The ONE home of the sizing formulas (vr/m1/m2/net/vperm): the host
    builder reaches it through `seg_classes`, the device builder through
    its histogram program — a drift between two copies would silently
    break device/host bit-parity."""
    in_classes = _build_classes(widths, counts)
    vr = _round32(in_classes[-1].vb) if in_classes else 32
    m1 = in_classes[-1].sb if in_classes else 0
    out_classes = _build_classes(owidths, ocounts)
    out_vb = out_classes[-1].vb if out_classes else 0
    m2 = out_classes[-1].sb if out_classes else 0
    n = _pow2_at_least(max(m1, m2))
    dummies = out_vb - v
    vp = _pow2_at_least(max(vr + dummies, out_vb, 32 * 128 * 2))
    return LayoutMeta(
        in_classes=tuple(in_classes), out_classes=tuple(out_classes),
        widths=widths, counts=counts, owidths=owidths, ocounts=ocounts,
        vr=vr, m1=m1, m2=m2, out_vb=out_vb, n=n, vp=vp,
    )


def seg_classes(in_w: np.ndarray, out_w: np.ndarray, v: int) -> LayoutMeta:
    """Degree widths -> aligned classes + every derived static size."""
    widths, counts = np.unique(in_w, return_counts=True)
    owidths, ocounts = np.unique(out_w, return_counts=True)
    return seg_classes_from_counts(widths, counts, owidths, ocounts, v)


def seg_relabel_in(in_w: np.ndarray, meta: LayoutMeta):
    """Class-major, old-id-minor relabeling (dst side): one vectorized
    `ranked_placement` pass (the shared classing helper) instead of a
    Python loop over classes."""
    v = int(in_w.shape[0])
    in_map = _width_class_map(meta.in_classes, meta.widths)
    in_va = np.array(
        [in_map[int(wv)].va for wv in meta.widths], dtype=np.int64
    )
    old2new = ranked_placement(
        np.searchsorted(meta.widths, in_w), in_va
    ).astype(np.int32)
    new2old = np.full(meta.vr, -1, dtype=np.int32)
    new2old[old2new] = np.arange(v, dtype=np.int32)
    return new2old, old2new


def seg_relabel_out(out_w: np.ndarray, meta: LayoutMeta):
    """Out-order positions (src side), same vectorized placement."""
    out_map = _width_class_map(meta.out_classes, meta.owidths)
    out_va = np.array(
        [out_map[int(wv)].va for wv in meta.owidths], dtype=np.int64
    )
    return ranked_placement(
        np.searchsorted(meta.owidths, out_w), out_va
    ).astype(np.int32)


def seg_relabel(in_w: np.ndarray, out_w: np.ndarray, meta: LayoutMeta):
    """Both sides of the relabeling (see `seg_relabel_in`/`_out`)."""
    new2old, old2new = seg_relabel_in(in_w, meta)
    return new2old, old2new, seg_relabel_out(out_w, meta)


def seg_l1_slots(src, dst, old2new, meta: LayoutMeta):
    """L1 slots: edges sorted by (dst_new, src); rank = in-row position
    (the one REQUIRED sort: rank order == canonical min-parent)."""
    dstn = _gather(old2new, dst)
    order1, rank1 = _sort_rank(dstn, src)
    base1, stride1 = _vertex_tables(meta.in_classes, meta.vr)
    ds = _gather(dstn, order1)
    l1_sorted = _slot_assign(base1, stride1, ds, rank1)  # slots < 2^28
    src_l1 = np.full(meta.m1, INF_DIST, dtype=np.int32)
    _scatter(src_l1, l1_sorted, _gather(src, order1))  # ORIGINAL ids
    l1_by_edge = np.empty(src.shape[0], dtype=np.int32)
    _scatter(l1_by_edge, order1, l1_sorted)
    return src_l1, l1_by_edge, dstn


def seg_l2_slots(src, outpos_of_old, meta: LayoutMeta):
    """L2 slots: edges grouped by src out-position.  The within-row rank is
    FREE (the big network routes any permutation and the broadcast fills
    every rank slot of a source with the same bit), so a single counting
    pass replaces the full (srcpos, dst) radix sort (measured
    272 s -> ~3 s at s25), assigning slots directly in edge order."""
    srcpos = _gather(outpos_of_old, src)
    rank2 = _rank_by_count(srcpos, meta.out_classes[-1].vb)
    base2, stride2 = _vertex_tables(meta.out_classes, meta.out_classes[-1].vb)
    return _slot_assign(base2, stride2, srcpos, rank2)


def seg_net_assembly(l1_by_edge, l2_by_edge, meta: LayoutMeta):
    """Big network permutation: L1 slot <- L2 slot, identity-padded."""
    net = np.full(meta.n, -1, dtype=np.int32)
    _scatter(net, l1_by_edge, l2_by_edge)
    used = np.zeros(meta.n, dtype=np.uint8)
    _mark_used(l2_by_edge, used)
    _pad_identity(net, used, meta.n)
    return net


def seg_vperm_assembly(outpos_of_old, old2new, meta: LayoutMeta):
    """Small network permutation: vertex-space words -> out-order words.
    Dummy out positions (padded rank-major class tails) must read zero:
    wire them to the guaranteed-zero input region [vr, vp)."""
    vperm = np.full(meta.vp, -1, dtype=np.int32)
    real_mask = np.zeros(meta.out_vb, dtype=bool)
    real_mask[outpos_of_old] = True
    # real out positions <- relabeled id of their owning vertex
    vperm[outpos_of_old] = old2new
    dummy_positions = np.flatnonzero(~real_mask)
    vperm[dummy_positions] = meta.vr + np.arange(dummy_positions.shape[0])
    used = np.zeros(meta.vp, dtype=np.uint8)
    _mark_used(vperm[vperm >= 0], used)
    _pad_identity(vperm, used, meta.vp)
    return vperm


def seg_csr(srcn, dstn, l1_by_edge, meta: LayoutMeta):
    """Sparse-path CSR over relabeled src ids.  Within-row order is free
    (the sparse superstep re-sorts its own gathered candidates), so a
    counting placement replaces the third full edge sort of the build."""
    return _csr_fill(srcn, dstn, l1_by_edge, meta.vr)


def build_relay_graph(graph: Graph | DeviceGraph) -> RelayGraph:
    """Build the full relay layout (host side, once per graph).

    Requires the native Beneš router; raises RuntimeError when unavailable.
    The body is a sequential composition of the ``seg_*`` segment functions
    above — the device builder (graph/relay_device.py) composes the SAME
    segments as its measured host arm, overlapped with the routes.
    """
    _ensure_build_log()
    benes.require_native()
    src, dst, v, e = extract_edges(graph)

    with _phase("degrees"):
        in_w, out_w = seg_degrees(src, dst, v)

    meta = seg_classes(in_w, out_w, v)
    new2old, old2new, outpos_of_old = seg_relabel(in_w, out_w, meta)

    with _phase("l1 slots"):
        src_l1, l1_by_edge, dstn = seg_l1_slots(src, dst, old2new, meta)
    with _phase("l2 slots"):
        l2_by_edge = seg_l2_slots(src, outpos_of_old, meta)
    with _phase("net perm assembly"):
        net = seg_net_assembly(l1_by_edge, l2_by_edge, meta)

    # One huge-page reservation held across BOTH routes (net + vperm):
    # per-route reserve/free cycles pay kernel compaction twice and the
    # second reservation can fall short on a fragmented allocator; the hold
    # covers the LARGER of the two routed networks (vp can exceed n on
    # vertex-heavy, edge-sparse graphs).
    with benes.hugepage_reservation(max(meta.n, meta.vp)):
        with _phase("net route"):
            net_masks_full = benes.route_std(net, trusted=True)
        with _phase("net compact"):
            net_masks, net_table = _compact_and_table(net_masks_full, meta.n)
            del net_masks_full
        with _phase("vperm route"):
            vperm = seg_vperm_assembly(outpos_of_old, old2new, meta)
            vperm_masks_full = benes.route_std(vperm, trusted=True)
            vperm_masks, vperm_table = _compact_and_table(
                vperm_masks_full, meta.vp
            )
            del vperm_masks_full

    with _phase("sparse CSR"):
        srcn = _gather(old2new, src)
        adj_indptr, adj_dst, adj_slot = seg_csr(srcn, dstn, l1_by_edge, meta)

    return RelayGraph(
        num_vertices=v,
        num_edges=e,
        vr=meta.vr,
        new2old=new2old,
        old2new=old2new,
        vperm_masks=vperm_masks,
        vperm_table=vperm_table,
        vperm_size=meta.vp,
        out_classes=meta.out_classes,
        out_space=meta.out_vb,
        net_masks=net_masks,
        net_table=net_table,
        net_size=meta.n,
        m1=meta.m1,
        m2=meta.m2,
        in_classes=meta.in_classes,
        src_l1=src_l1,
        adj_indptr=adj_indptr.astype(np.int32),
        adj_dst=adj_dst,
        adj_slot=adj_slot,
    )


@dataclass(frozen=True)
class ShardedRelayGraph:
    """Per-shard relay layouts (v4) with ONE unified class structure.

    The multi-device TPU-fast layout: shard ``s`` owns a contiguous block of
    the (globally relabeled) vertex space and holds the relay pipeline for
    exactly its owned destinations — its own vperm network, degree-class
    broadcast, Beneš edge net and src-id tables — while all shards share the
    SAME static shapes (class slices, network sizes, stage tables), so one
    `shard_map` program runs everywhere and only the mask/table DATA differs
    per device (stacked on axis 0).  Ownership is CLASS-BALANCED (each
    in-degree class dealt across shards — see the builder), so the shared
    shapes are ~1/n of the single-chip layout instead of approaching it on
    skewed graphs.  The per-superstep exchange is the
    bit-packed frontier all-gather (1 bit/vertex over ICI); with v4's
    standard packing the gathered words ARE the global standard-packed
    frontier (relabeling is shard-major), so they feed each shard's vperm
    directly with no repacking at all.
    """

    num_vertices: int
    num_edges: int
    num_shards: int
    block: int  # owned vertex slots per shard (multiple of 32)
    new2old: np.ndarray  # int32[n*block]; -1 at dummies
    old2new: np.ndarray  # int32[V]
    vperm_masks: np.ndarray  # uint32[n, vperm_words]
    vperm_table: tuple[StageSpec, ...]
    vperm_size: int
    out_classes: tuple[ClassSlice, ...]
    out_space: int
    net_masks: np.ndarray  # uint32[n, net_words]
    net_table: tuple[StageSpec, ...]
    net_size: int
    m1: int
    m2: int
    in_classes: tuple[ClassSlice, ...]  # over local [0, block)
    src_l1: np.ndarray  # int32[n, m1]; ORIGINAL src ids, INF padding
    # Per-shard dst-owned adjacency (ROADMAP item 1 / ISSUE 11): shard s's
    # CSR over GLOBAL relabeled src ids holding, per edge into an owned
    # destination, the LOCAL dst id [0, block) and that edge's L1 slot —
    # the operands the sharded push (sparse gather) superstep needs, so
    # the direction-optimizing schedule runs across the mesh.  Rows are
    # padded to the max per-shard edge count for uniform SPMD shapes.
    # ``outdeg`` is the per-GLOBAL-new-id out-degree table (0 at dummies)
    # the Beamer predicate reads.  None on layouts built before this
    # field existed (dense-only fallback).
    adj_indptr: np.ndarray | None = None  # int32[n, n*block + 2]
    adj_dst: np.ndarray | None = None  # int32[n, emax]; LOCAL dst ids
    adj_slot: np.ndarray | None = None  # int32[n, emax]; L1 slots
    outdeg: np.ndarray | None = None  # int32[n*block]


def _merge_tables(tables: list[tuple[StageSpec, ...]]) -> tuple[StageSpec, ...]:
    """Shared static stage table for stacked per-shard masks: identical
    layout (same net size -> same offsets), per-stage nonzero range = union
    over shards."""
    out = []
    for specs in zip(*tables):
        st = specs[0]
        out.append(
            st._replace(
                lo=min(s.lo for s in specs), hi=max(s.hi for s in specs)
            )
        )
    return tuple(out)


def _unified_classes(widths: np.ndarray, per_shard_counts: np.ndarray):
    """Aligned classes from per-width counts maxed over shards.
    ``per_shard_counts``: [num_widths, n]."""
    return _build_classes(widths, per_shard_counts.max(axis=1))


def build_sharded_relay_graph(
    graph: Graph | DeviceGraph, num_shards: int
) -> ShardedRelayGraph:
    """Build per-shard relay layouts (v4) with a unified static structure.

    Ownership is CLASS-BALANCED (per-shard class structure): each
    in-degree class is dealt across the shards in equal contiguous chunks
    (ascending original id within a chunk), so every shard's per-width
    class count is within 1 of ``count/n`` and the shared static envelope
    (max over shards) is TIGHT.  The old contiguous-original-id partition
    let a skewed degree distribution concentrate a class in one shard,
    making the unified max-over-shards counts approach the SINGLE-CHIP
    class sizes — every shard then padded, routed and row-minned close to
    the whole graph's slot space, the x8 padded-work amplification behind
    the non-monotone sharded scaling of BENCHMARKS row 12 (VERDICT r5
    weak #5).  With balanced classes, per-shard slots shrink ~1/n and the
    compact frontier exchange stays flat (it ships real words only,
    parallel/sharded._own_word_table).

    Vertices are relabeled within each shard so in-degree classes are
    contiguous; the global new-id space is the concatenation of shard
    blocks (ownership itself is an arbitrary bijection — every consumer
    goes through ``old2new``/``new2old``).
    """
    _ensure_build_log()
    benes.require_native()
    if num_shards < 1:
        raise ValueError("num_shards must be >= 1")
    from .csr import _sorted_by_dst, unpad_edges

    if isinstance(graph, DeviceGraph):
        src, dst = _sorted_by_dst(*unpad_edges(graph))
    else:
        src, dst = _sorted_by_dst(graph.src, graph.dst)
    src = src.astype(np.int64)
    dst = dst.astype(np.int64)
    v = graph.num_vertices
    e = int(src.shape[0])
    n = num_shards

    indeg = np.bincount(dst, minlength=v)
    in_w = _class_width(indeg)

    # ---- class-balanced ownership (see docstring) --------------------------
    shard_of_old = np.empty(v, dtype=np.int64)
    order_v = np.argsort(in_w, kind="stable")
    pos = 0
    for wv, cnt in zip(*np.unique(in_w, return_counts=True)):
        ids = order_v[pos : pos + cnt]
        shard_of_old[ids] = (np.arange(cnt, dtype=np.int64) * n) // cnt
        pos += cnt
    assert pos == v

    # ---- unified in-classes: per-width counts maxed over shards ------------
    # (The max is now within 1 of the mean by construction.)
    widths_all = np.unique(in_w)
    nwidths = int(widths_all.shape[0])
    in_widx = np.searchsorted(widths_all, in_w).astype(np.int64)
    counts = (
        np.bincount(shard_of_old * nwidths + in_widx, minlength=n * nwidths)
        .reshape(n, nwidths)
        .T
    )
    in_classes = _unified_classes(widths_all, counts)
    block = _round32(in_classes[-1].vb)
    m1 = in_classes[-1].sb

    # ---- relabel: shard-major, class-major, old-id-minor -------------------
    # One vectorized `ranked_placement` pass over (shard, width) groups
    # replaces the old per-shard-per-width Python loop (the classing
    # helpers the device builder extracted, reused host-side — ISSUE 10):
    # a vertex's new id is shard base + class slot start + its stable rank
    # within the (shard, width) group, ordered by old id.
    width_to_class = _width_class_map(in_classes, widths_all)
    va_by_widx = np.array(
        [width_to_class[int(wv)].va for wv in widths_all], dtype=np.int64
    )
    group_base = (
        np.arange(n, dtype=np.int64)[:, None] * block + va_by_widx[None, :]
    ).reshape(-1)
    old2new = ranked_placement(shard_of_old * nwidths + in_widx, group_base)
    new2old = np.full(n * block, -1, dtype=np.int64)
    new2old[old2new] = np.arange(v, dtype=np.int64)

    # ---- edge shard slices: grouped by the OWNER of the destination --------
    # Ownership is class-balanced (not contiguous in original ids), so the
    # per-shard edge sets come from a stable group-by instead of a
    # searchsorted over the dst-sorted array; dst order is preserved
    # within each shard's slice.
    owner_e = shard_of_old[dst]
    order_e = np.argsort(owner_e, kind="stable")
    src = src[order_e]
    dst = dst[order_e]
    bounds = np.concatenate(
        [[0], np.cumsum(np.bincount(owner_e, minlength=n))]
    ).astype(np.int64)

    # ---- unified out-classes over per-shard out-degrees --------------------
    out_sparse = []
    owidth_counts: dict[int, int] = {}
    for s in range(n):
        es, ee = bounds[s], bounds[s + 1]
        uids, ucounts = np.unique(src[es:ee], return_counts=True)
        w = _class_width(ucounts)
        out_sparse.append((uids, w))
        for wv, c in zip(*np.unique(w, return_counts=True)):
            owidth_counts[int(wv)] = max(owidth_counts.get(int(wv), 0), int(c))
    owidths = np.array(sorted(owidth_counts), dtype=np.int64)
    ocounts = np.array([owidth_counts[int(w)] for w in owidths], dtype=np.int64)
    out_classes = _build_classes(owidths, ocounts)
    out_vb = out_classes[-1].vb
    m2 = out_classes[-1].sb
    out_width_to_class = _width_class_map(out_classes, owidths)

    # ---- network sizes (shared across shards) ------------------------------
    net_size = _pow2_at_least(max(m1, m2))
    gtot = n * block
    max_dummies = max(
        int(out_vb - u.shape[0]) for u, _ in out_sparse
    )
    vp = _pow2_at_least(max(gtot + max_dummies, out_vb, 32 * 128 * 2))

    base1, stride1 = _vertex_tables(in_classes, block)
    base2, stride2 = _vertex_tables(out_classes, out_vb)

    vperm_masks_l, vperm_tables = [], []
    net_masks_l, net_tables = [], []
    src_l1 = np.full((n, m1), INF_DIST, dtype=np.int32)
    adj_parts: list = []  # futures during the loop, (indptr, dst, slot) after

    # Static out-class lookup tables for the vectorized per-shard classing
    # below (shared helpers with the device builder — ISSUE 10 satellite):
    # position -> owning class (classes are contiguous [va, vb)) -> width
    # index, plus each width's class slot start.
    va_by_owidx = np.array(
        [out_width_to_class[int(w)].va for w in owidths], dtype=np.int64
    )
    ova_bounds = np.array([c.va for c in out_classes], dtype=np.int64)
    owidx_of_cls = np.searchsorted(
        owidths, np.array([c.real_width for c in out_classes], dtype=np.int64)
    )
    owidx_of_pos = owidx_of_cls[
        np.searchsorted(ova_bounds, np.arange(out_vb), side="right") - 1
    ]

    # One huge-page hold across all 2n per-shard routes (see the
    # single-shard builder for why per-route reserve/free cycles lose).
    with benes.hugepage_reservation(max(net_size, vp)):
        for s in range(n):
            uids_s, uw_s = out_sparse[s]
            # out positions for this shard's sources (ascending ORIGINAL id
            # within each width class): one ranked_placement pass instead
            # of the per-width Python loop.
            owidx_s = np.searchsorted(owidths, uw_s).astype(np.int64)
            outpos_s = ranked_placement(owidx_s, va_by_owidx)
            outpos_of_old = np.full(v, -1, dtype=np.int64)
            outpos_of_old[uids_s] = outpos_s
            vperm = np.full(vp, -1, dtype=np.int32)
            vperm[outpos_s] = old2new[uids_s]
            # Dummy out positions: tails of classes PRESENT in this shard
            # get dummy ids first, walked in ascending-width class order
            # with positions ascending within a class (the old
            # dummy_cursor sequence, which is NOT ascending-position when
            # a small-width vertex-major class follows a larger rank-major
            # va); then positions of absent classes, ascending.
            front = vperm[:out_vb]
            cnt_by_owidx = np.bincount(owidx_s, minlength=owidths.shape[0])
            present = cnt_by_owidx[owidx_of_pos] > 0
            tail = np.flatnonzero((front < 0) & present)
            tail = tail[np.argsort(owidx_of_pos[tail], kind="stable")]
            front[tail] = gtot + np.arange(tail.shape[0], dtype=np.int64)
            missing = np.flatnonzero(front < 0)
            vperm[missing] = (
                gtot + tail.shape[0] + np.arange(missing.shape[0])
            )
            used = np.zeros(vp, dtype=bool)
            used[vperm[vperm >= 0]] = True
            _pad_identity(vperm, used, vp)
            vm_full = benes.route_std(vperm, trusted=True)
            vm, vt = _compact_and_table(vm_full, vp)
            del vm_full
            vperm_masks_l.append(vm)
            vperm_tables.append(vt)

            # ---- L1/L2 slots for this shard's edges ----------------------------
            es, ee = bounds[s], bounds[s + 1]
            s_src, s_dst = src[es:ee], dst[es:ee]
            dstn = old2new[s_dst] - s * block  # local [0, block)
            o1, r1 = _sort_rank(dstn.astype(np.int32), s_src.astype(np.int32))
            ds = dstn[o1]
            l1_sorted = base1[ds] + r1.astype(np.int64) * stride1[ds]
            src_l1[s, l1_sorted] = s_src[o1].astype(np.int32)

            srcpos = outpos_of_old[s_src]
            o2, r2 = _sort_rank(srcpos.astype(np.int32), dstn.astype(np.int32))
            sp = srcpos[o2]
            l2_sorted = base2[sp] + r2.astype(np.int64) * stride2[sp]

            net = np.full(net_size, -1, dtype=np.int64)
            l1_by_edge = np.empty(ee - es, dtype=np.int64)
            l1_by_edge[o1] = l1_sorted
            l2_by_edge = np.empty(ee - es, dtype=np.int64)
            l2_by_edge[o2] = l2_sorted

            # ---- per-shard dst-owned adjacency (the push body's CSR) ---
            # Grouped by GLOBAL relabeled src id — the all-gathered
            # frontier's id space — holding (local dst, L1 slot) per
            # edge; the within-row order is free (the push superstep
            # re-sorts its gathered candidates by (dst, slot)), so the
            # shared counting-sort fill (`_csr_fill`, native fast path)
            # does it in one pass, same as the single-chip builder's
            # sparse CSR segment.  Submitted to the device builder's
            # worker pool BEFORE this shard's net route starts (the
            # PR 10 overlap idiom: the route is walker-bound on one
            # core, the fill is numpy on another), resolved after the
            # loop.
            from .relay_device import _TRACK_POOL

            srcn_g = old2new[s_src].astype(np.int32)
            adj_parts.append(
                _TRACK_POOL.submit(
                    _csr_fill, srcn_g, dstn.astype(np.int32),
                    l1_by_edge.astype(np.int32), gtot,
                )
            )

            net[l1_by_edge] = l2_by_edge
            used = np.zeros(net_size, dtype=bool)
            used[l2_by_edge] = True
            _pad_identity(net, used, net_size)
            nm_full = benes.route_std(net, trusted=True)
            nm, nt = _compact_and_table(nm_full, net_size)
            del nm_full
            net_masks_l.append(nm)
            net_tables.append(nt)

    # Resolve the overlapped adjacency fills (re-raises a worker failure).
    adj_parts = [p.result() for p in adj_parts]

    # Uniform SPMD shapes for the adjacency rows: pad every shard's edge
    # arrays to the max per-shard count (padded tail entries are never
    # addressed — each shard's indptr bounds its own real entries).
    emax = max(1, max(p[1].shape[0] for p in adj_parts))
    adj_indptr = np.stack([p[0] for p in adj_parts])
    adj_dst = np.zeros((n, emax), np.int32)
    adj_slot = np.zeros((n, emax), np.int32)
    for s, (_, d_s, sl_s) in enumerate(adj_parts):
        adj_dst[s, : d_s.shape[0]] = d_s
        adj_slot[s, : sl_s.shape[0]] = sl_s
    outdeg_new = np.zeros(gtot, np.int32)
    outdeg_new[old2new] = np.bincount(src, minlength=v).astype(np.int32)

    return ShardedRelayGraph(
        num_vertices=v,
        num_edges=e,
        num_shards=n,
        block=block,
        new2old=new2old.astype(np.int32),
        old2new=old2new.astype(np.int32),
        vperm_masks=np.stack(vperm_masks_l),
        vperm_table=_merge_tables(vperm_tables),
        vperm_size=vp,
        out_classes=tuple(out_classes),
        out_space=out_vb,
        net_masks=np.stack(net_masks_l),
        net_table=_merge_tables(net_tables),
        net_size=net_size,
        m1=m1,
        m2=m2,
        in_classes=tuple(in_classes),
        src_l1=src_l1,
        adj_indptr=adj_indptr,
        adj_dst=adj_dst,
        adj_slot=adj_slot,
        outdeg=outdeg_new,
    )


def _width_class_map(classes, widths: np.ndarray):
    """Map REAL (pre-padding) width -> its ClassSlice."""
    del widths
    return {int(c.real_width): c for c in classes}


def _pad_identity(perm: np.ndarray, used: np.ndarray, n: int) -> None:
    """Complete a partial mapping to a bijection, wiring free outputs to free
    inputs IDENTITY-first: output j takes input j wherever both are free.
    Where both members of a stage pair are pads, identity wiring routes
    switch-free (StageSpec.lo/hi shrink); mixed live/pad pairs still switch.
    ``used`` is uint8 (or bool) and is updated in place; the native two-scan
    replaces the numpy multi-pass at big nets."""
    try:
        from .native_gen import native_available, pad_identity_native

        if (
            native_available()
            and used.dtype == np.uint8
            and perm.dtype == np.int32
        ):
            pad_identity_native(perm, used)
            return
    except Exception:
        pass
    free_out = perm < 0
    unused = used == 0  # dtype-safe (uint8 bitwise ~ would misfire)
    both = free_out & unused
    idx = np.flatnonzero(both)
    perm[idx] = idx
    used[idx] = 1
    free_outputs = np.flatnonzero(perm < 0)
    free_inputs = np.flatnonzero(used == 0)
    if free_outputs.shape[0] != free_inputs.shape[0]:
        raise ValueError("partial permutation is not completable")
    perm[free_outputs] = free_inputs
    used[free_inputs] = 1


# --------------------------------------------------------------------------
# Serialization: RelayGraph <-> flat numpy arrays.  The persistent layout
# cache (bfs_tpu/cache/layout.py) stores exactly this mapping as one on-disk
# bundle; keeping the converters next to the dataclass means a field added
# to RelayGraph fails loudly here instead of silently dropping from bundles.
# --------------------------------------------------------------------------

def classes_to_rows(classes) -> np.ndarray:
    """Pack ClassSlice tuples into an int64[n, 8] row table."""
    return np.array(
        [
            [c.width, c.va, c.vb, c.sa, c.sb, c.real, int(c.vertex_major),
             c.real_width]
            for c in classes
        ],
        dtype=np.int64,
    ).reshape(-1, 8)


def rows_to_classes(rows: np.ndarray) -> tuple[ClassSlice, ...]:
    return tuple(
        ClassSlice(
            width=int(r[0]), va=int(r[1]), vb=int(r[2]), sa=int(r[3]),
            sb=int(r[4]), real=int(r[5]), vertex_major=bool(r[6]),
            real_width=int(r[7]),
        )
        for r in np.asarray(rows).tolist()
    )


def table_to_rows(table) -> np.ndarray:
    """Pack StageSpec tuples into an int64[n, 6] row table."""
    return np.array(
        [[t.d, t.offset, t.nwords, int(t.compact), t.lo, t.hi] for t in table],
        dtype=np.int64,
    ).reshape(-1, 6)


def rows_to_table(rows: np.ndarray) -> tuple[StageSpec, ...]:
    return tuple(
        StageSpec(
            d=int(r[0]), offset=int(r[1]), nwords=int(r[2]),
            compact=bool(r[3]), lo=int(r[4]), hi=int(r[5]),
        )
        for r in np.asarray(rows).tolist()
    )


def relay_to_arrays(rg: RelayGraph) -> dict[str, np.ndarray]:
    """Flatten a RelayGraph to name -> ndarray (scalars as 0-d arrays)."""
    return dict(
        num_vertices=np.int64(rg.num_vertices),
        num_edges=np.int64(rg.num_edges),
        vr=np.int64(rg.vr),
        new2old=rg.new2old,
        old2new=rg.old2new,
        vperm_masks=rg.vperm_masks,
        vperm_table=table_to_rows(rg.vperm_table),
        vperm_size=np.int64(rg.vperm_size),
        out_classes=classes_to_rows(rg.out_classes),
        out_space=np.int64(rg.out_space),
        net_masks=rg.net_masks,
        net_table=table_to_rows(rg.net_table),
        net_size=np.int64(rg.net_size),
        m1=np.int64(rg.m1),
        m2=np.int64(rg.m2),
        in_classes=classes_to_rows(rg.in_classes),
        src_l1=rg.src_l1,
        adj_indptr=rg.adj_indptr,
        adj_dst=rg.adj_dst,
        adj_slot=rg.adj_slot,
    )


def relay_from_arrays(z) -> RelayGraph:
    """Inverse of :func:`relay_to_arrays`.  ``z`` is any mapping of
    name -> array (an npz file, a dict of memmaps, ...); big arrays are
    taken as-is, so memmap-backed loads stay lazy."""
    return RelayGraph(
        num_vertices=int(z["num_vertices"]),
        num_edges=int(z["num_edges"]),
        vr=int(z["vr"]),
        new2old=z["new2old"],
        old2new=z["old2new"],
        vperm_masks=z["vperm_masks"],
        vperm_table=rows_to_table(z["vperm_table"]),
        vperm_size=int(z["vperm_size"]),
        out_classes=rows_to_classes(z["out_classes"]),
        out_space=int(z["out_space"]),
        net_masks=z["net_masks"],
        net_table=rows_to_table(z["net_table"]),
        net_size=int(z["net_size"]),
        m1=int(z["m1"]),
        m2=int(z["m2"]),
        in_classes=rows_to_classes(z["in_classes"]),
        src_l1=z["src_l1"],
        adj_indptr=np.asarray(z["adj_indptr"], dtype=np.int32),
        adj_dst=z["adj_dst"],
        adj_slot=z["adj_slot"],
    )


def valid_slot_words(src_l1: np.ndarray, net_size: int) -> np.ndarray:
    """Static valid-slot bitmask (STANDARD packing): uint32[net_size/32], bit
    set iff that L1 slot holds a real edge.  Beneš pad routing may deliver
    stray 1-bits to padded slots; this mask zeroes them before the row-min."""
    m1 = src_l1.shape[0]
    bits = np.zeros(net_size, dtype=bool)
    bits[:m1] = src_l1 != np.int32(INF_DIST)
    return np.packbits(
        bits.reshape(-1, 32), axis=1, bitorder="little"
    ).view(np.uint32).reshape(-1)
