"""Pallas TPU kernels for the v4 relay superstep's Beneš networks.

The XLA path runs one kernel per stage — an HBM round-trip of the word
array plus ~0.4 ms launch overhead each (measured; 55 stages at net 2^28).
Here the stages factor into at most three fused passes per network with the
word array VMEM-resident and only the per-stage masks DMA-streamed (the
masks are the irreducible traffic):

viewing the standard-packed words as [R, 128] and a stage's element
distance d as

  * an intra-word bit distance d          (d < 32, elementwise)
  * a lane distance d/32                  (32 <= d < 4096)
  * a row distance d/4096                 (4096 <= d < TR*4096)
  * an outer-block distance d/4096/TR     (above)

pass B fuses the consecutive middle run (d < TR*4096) on [TR, 128] tiles;
passes A/C fuse the outer prefix/suffix on [B, tt, 128] blocks.  v4
additionally (a) streams PAIR-COMPACTED masks for d >= 4096 — half the
words are structurally zero (graph/relay.py) — and (b) skips DMA + compute
for pass-B tiles outside a stage's static nonzero range (the
identity-wired tail).  Outer-stage masks are re-chunked host-side
(:func:`prepare_pass_masks`) so every DMA is one contiguous row slice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import knobs
from ..graph.relay import StageSpec

LANES = 128
#: pass-B tile rows: 256 rows * 128 lanes * 4 B = 128 KB of VMEM for x.
#: Mosaic unrolls each stage over the tile's vregs, so cold compile time
#: grows steeply with the tile (PERF.md, PR 21).  Env-tunable for on-chip
#: sweeps.
TILE_ROWS = knobs.get("BFS_TPU_TILE_ROWS")
#: outer-pass inner-chunk rows; the x block is (B, OUTER_TT, 128).
OUTER_TT = knobs.get("BFS_TPU_OUTER_TT")
#: mask-DMA pipeline depth (buffers per pass).  2 = classic double
#: buffering: stage si+1's DMA is issued when stage si starts computing.
#: The per-stage mask DMA is ~0.5-1 MB, whose issue+semaphore latency
#: exceeds its transfer time, so at depth 2 the pipeline is
#: issue-latency-bound; deeper prefetch (4) keeps more copies in flight.
#: Only relevant on the per-stage path (BFS_TPU_TM=0).
DMA_DEPTH = max(2, knobs.get("BFS_TPU_DMA_DEPTH"))

#: Tile-major pass-B mask streaming: the local pass's masks are relaid
#: host-side so ALL ~45 stages' rows for one x-tile are contiguous, and the
#: kernel fetches them in ONE ~36 MB DMA per tile (double-buffered across
#: grid steps) instead of ~45 per-stage ~0.5-1 MB copies.  Measured on the
#: bench chip (interleaved same-process A/B at s24): marginally faster
#: than the per-stage path in mixed windows (46-54 vs 48-62 ms/apply) and
#: equal in the chip's write-collapsed windows, where both are bound by
#: the pass outputs' HBM writes, not the mask reads — amortized probes
#: showed read streaming at 163-449 GB/s at EVERY DMA size while
#: read+write paths collapsed to ~1 GB/s (docs/ARCHITECTURE.md §8).  Kept
#: as default for the structural simplicity (no DMA-depth tuning).
#: Incompatible with BFS_TPU_LANE_COMPACT (which keeps the per-stage
#: path).
TILE_MAJOR = knobs.get("BFS_TPU_TM")


def _tile_major_enabled() -> bool:
    return TILE_MAJOR and not knobs.get("BFS_TPU_LANE_COMPACT")

#: Tail-range DMA/compute guards (static per stage, dynamic per tile).  At
#: m1 ~ 0.94n the skippable ranges are tiny while the conditional DMAs can
#: cost pipeline overlap — BFS_TPU_GUARDS=0 disables them for measurement.
_GUARDS = knobs.get("BFS_TPU_GUARDS")


def pallas_enabled() -> bool:
    """Use the Pallas path on TPU backends (the CPU test platform runs the
    pure-XLA stages).  BFS_TPU_PALLAS=0/1 overrides."""
    env = knobs.get("BFS_TPU_PALLAS")
    if env in ("0", "1"):
        return env == "1"
    return jax.default_backend() == "tpu"


def pallas_net_ok(n: int) -> bool:
    """The fused passes need at least a [128, 128]-word view."""
    return n // 32 // LANES >= 128


def split_passes(table: tuple[StageSpec, ...], n: int, tile_rows: int = TILE_ROWS):
    """(prefix outer stages, local run, suffix outer stages, tr)."""
    r = n // 32 // LANES
    tr = min(tile_rows, max(r, 1))
    # Env overrides (BFS_TPU_TILE_ROWS / BFS_TPU_OUTER_TT) must keep the
    # grid exact: a tr that does not divide r makes ``grid = r // tr`` drop
    # the tail rows and both local paths then silently produce a wrong
    # permutation (ADVICE r4).  Fail loudly instead.
    if r > 1:
        if tr <= 0 or r % tr:
            raise ValueError(
                f"tile_rows={tr} does not divide the {r}-row network view; "
                "pick a power-of-two BFS_TPU_TILE_ROWS that divides it"
            )
        tt = min(OUTER_TT, tr)
        if tt <= 0 or tr % tt:
            raise ValueError(
                f"BFS_TPU_OUTER_TT={OUTER_TT} does not divide tile_rows={tr}"
            )
    local = [i for i, st in enumerate(table) if st.d < tr * 4096]
    assert local, "no local stages — network too small for the fused path"
    lo, hi = local[0], local[-1] + 1
    assert local == list(range(lo, hi)), "local stages must be consecutive"
    return list(range(lo)), list(range(lo, hi)), list(range(hi, len(table))), tr


#: Lane-distance stages (32 <= d < 4096) store mask bits only at the lower
#: lane of each pair — exactly 50% structurally-zero words in the flat
#: stream (tools/mask_sparsity.py).  For word distances dw >= this bound
#: the prepared pass-B operand drops the zero lanes ([r, 64] blocks, a
#: separate side array) and the kernel re-expands with <= 2 conditional
#: lane rolls; smaller dw would need too many relayout pieces.  Saves
#: ~100 MB of the s24 net mask stream per superstep.
LANE_COMPACT_MIN_DW = 16


def _lane_compactable(st: StageSpec) -> bool:
    """Default OFF by measurement (round 4, interleaved same-process A/B at
    ~200 GB/s DMA): the in-kernel expansion relayouts (sublane repeat +
    conditional lane rolls) cost ~1 ms MORE per net apply than the ~100 MB
    of zero-lane DMA they save (~7.3 vs ~6.5 ms).  The trade flips in
    DMA-starved windows (3-27 GB/s was typical in round 3, where 100 MB is
    4-30 ms) — hence BFS_TPU_LANE_COMPACT=1 as an opt-in switch rather
    than dead code."""
    if not knobs.get("BFS_TPU_LANE_COMPACT"):
        return False
    return (
        32 <= st.d < 4096
        and not st.compact
        and (st.d >> 5) >= LANE_COMPACT_MIN_DW
    )


def _is_lane_compact(st: StageSpec) -> bool:
    """Pass-local spec marker: lane-compacted stages are flagged compact
    with d < 4096 (the stored table never pair-compacts below 4096)."""
    return bool(st.compact) and st.d < 4096


def _stage_rows(st: StageSpec, tr: int) -> int:
    """Storage rows a local-pass stage spans within one x-tile of ``tr``
    rows: pair-compacted (and lane-compacted) stages store half.  THE
    single definition — the host relayout (pass_static /
    prepare_pass_masks) and the kernels' buffer offsets must agree on it
    exactly."""
    return (tr // 2) if st.compact else tr


def pass_static(
    table: tuple[StageSpec, ...], n: int,
    tile_rows: int = TILE_ROWS, outer_tt: int = OUTER_TT,
):
    """Static (hashable) per-pass info: ``((mode, tr, tt, specs), ...)`` in
    execution order, with outer-stage specs rewritten to their local offsets
    in the rearranged arrays.  Must mirror :func:`prepare_pass_masks`.
    Lane-compactable local stages are flagged (compact=True, d < 4096) with
    offsets into the side lane64 array."""
    pre, local, suf, tr = split_passes(table, n, tile_rows)
    tt = min(outer_tt, tr)
    out = []

    def outer(idx):
        specs = []
        off = 0
        for i in idx:
            st = table[i]
            specs.append(st._replace(offset=off, nwords=st.nwords,
                                     lo=0, hi=st.nwords))
            off += st.nwords
        return ("outer", tr, tt, tuple(specs))

    if pre:
        out.append(outer(pre))
    if _tile_major_enabled():
        # Tile-major local pass: specs' offsets become WORD offsets within
        # one tile's concatenated mask block (all stages' rows for that
        # tile contiguous — ONE DMA per tile).  lo/hi keep their
        # within-stage word semantics for the compute guards.
        row_off = 0
        tm_specs = []
        for i in local:
            st = table[i]
            tm_specs.append(st._replace(offset=row_off * LANES))
            row_off += _stage_rows(st, tr)
        out.append(("local_tm", tr, tt, tuple(tm_specs)))
    else:
        lane_off = 0
        local_specs = []
        for i in local:
            st = table[i]
            if _lane_compactable(st):
                half = st.nwords // 2
                local_specs.append(
                    st._replace(compact=True, offset=lane_off, nwords=half,
                                lo=0, hi=half)
                )
                lane_off += half
            else:
                local_specs.append(st)
        out.append(("local", tr, tt, tuple(local_specs)))
    if suf:
        out.append(outer(suf))
    return tuple(out)


def prepare_pass_masks(
    masks_flat: np.ndarray, table: tuple[StageSpec, ...], n: int,
    tile_rows: int = TILE_ROWS, outer_tt: int = OUTER_TT,
):
    """Host-side, once per layout: per-pass mask arrays + local stage specs.

    Pass B reuses the stored layout as-is (stage tiles are already
    contiguous row slices).  Outer passes get rearranged copies: a stage
    stored (span, tr, LANES) becomes chunk-major (tr/tt, span, tt, LANES) so
    each grid step's mask block is ONE contiguous DMA.
    Returns ``[(mode, tr, tt, specs, array2d), ...]`` in execution order.
    """
    pre, local, suf, tr = split_passes(table, n, tile_rows)
    r = n // 32 // LANES
    b = r // tr
    tt = min(outer_tt, tr)
    arrays = []

    def outer_arr(idx):
        parts = []
        for i in idx:
            st = table[i]
            assert st.compact, "outer stages are always pair-compacted"
            span = b // 2
            w = masks_flat[st.offset : st.offset + st.nwords]
            parts.append(
                w.reshape(span, tr // tt, tt, LANES)
                .swapaxes(0, 1)
                .reshape(-1, LANES)
            )
        return (
            np.concatenate(parts)
            if parts
            else np.zeros((0, LANES), np.uint32)
        )

    if pre:
        arrays.append(outer_arr(pre))
    if _tile_major_enabled():
        # Tile-major local array: for each x-tile, all local stages' row
        # slices concatenated (mirrors pass_static's "local_tm" offsets).
        m2d = masks_flat.reshape(-1, LANES)
        ntiles = max(r // tr, 1)
        tile_parts = []
        for pid in range(ntiles):
            for i in local:
                st = table[i]
                rows = _stage_rows(st, tr)
                base = st.offset // LANES + pid * rows
                tile_parts.append(m2d[base : base + rows])
        arrays.append(
            np.ascontiguousarray(np.concatenate(tile_parts))
            if tile_parts
            else np.zeros((0, LANES), np.uint32)
        )
    else:
        arrays.append(masks_flat.reshape(-1, LANES))
        # Side array for lane-compacted local stages: even-group lanes only
        # ([r, 64] per stage, concatenated).  Appended directly after the
        # local array; apply_benes_fused consumes both for the local pass.
        lane_parts = []
        for i in local:
            st = table[i]
            if _lane_compactable(st):
                dw = st.d >> 5
                w = masks_flat[st.offset : st.offset + st.nwords].reshape(
                    -1, LANES
                )
                lanes = np.arange(LANES)
                lane_parts.append(
                    np.ascontiguousarray(w[:, (lanes & dw) == 0]).reshape(-1)
                )
        if lane_parts:
            # [-1, 128] storage (HBM DMA slices must be 128-lane aligned):
            # storage row q packs x-rows 2q | 2q+1's compacted 64 lanes.
            arrays.append(np.concatenate(lane_parts).reshape(-1, LANES))
    if suf:
        arrays.append(outer_arr(suf))
    return arrays


def _kroll(x, shift: int, axis: int, interpret: bool):
    """In-kernel roll by a STATIC shift.  pltpu.roll in compiled mode —
    jnp.roll's closed_call lowering hits an MLIR cache bug when several
    Pallas kernels in one program contain same-shaped rolls."""
    size = x.shape[axis]
    if interpret:
        return jnp.roll(x, shift % size, axis)
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, shift % size, axis)


def _stage_local(x, m, st: StageSpec, interpret: bool):
    """One butterfly stage on a pass-B tile x: (tr, LANES)."""
    d = st.d
    if d < 32:
        sh = jnp.uint32(d)
        t = (x ^ (x >> sh)) & m
        return x ^ t ^ (t << sh)
    dw = d >> 5
    if dw < LANES:  # lane butterfly; mask bits live at lower pair lanes
        idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        has = (idx & dw) != 0
        if _is_lane_compact(st):
            # m: (tr/2, 128) — storage row q packs x-rows 2q|2q+1's
            # compacted (even-group-lane) masks in its two 64-lane halves.
            # Reconstruct mv (tr, 128) whose EVEN-GROUP lanes hold the
            # stage's mask (odd-group lanes end up garbage, which is fine:
            # m_both only reads even-group lanes of mv — directly at even
            # lanes, rolled by dw at odd ones):
            #   1. sublane-double so each x-row sees its storage row,
            #   2. odd x-rows take the upper 64-lane half,
            #   3. duplicate the low half across the lane dim,
            #   4. shift each 2s-lane block into place (largest shift
            #      first — the selects test the DESTINATION lane, so
            #      composition goes coarse to fine).
            mcr = jnp.repeat(m, 2, axis=0)
            row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
            a = jnp.where(
                (row & 1) != 0, _kroll(mcr, -64, 1, interpret), mcr
            )
            mv = jnp.where(idx >= 64, _kroll(a, 64, 1, interpret), a)
            s = 32
            while s >= dw:
                mv = jnp.where(
                    (idx & (2 * s)) != 0, _kroll(mv, s, 1, interpret), mv
                )
                s //= 2
        else:
            mv = m
        partner = jnp.where(
            has, _kroll(x, dw, 1, interpret), _kroll(x, -dw, 1, interpret)
        )
        m_both = jnp.where(has, _kroll(mv, dw, 1, interpret), mv)
        return x ^ ((x ^ partner) & m_both)
    rw = dw // LANES  # row butterfly; compact mask (tr/2 rows)
    a = x.shape[0] // (2 * rw)
    xr = x.reshape(a, 2, rw, LANES)
    lo, hi = xr[:, 0], xr[:, 1]
    t = (lo ^ hi) & m.reshape(a, rw, LANES)
    return jnp.stack([lo ^ t, hi ^ t], axis=1).reshape(x.shape)


def _stage_outer(x, m, st: StageSpec, tr: int):
    """One outer-block butterfly on a pass-A/C block x: (B, tt, LANES);
    m: (B/2, tt, LANES) pair-compacted."""
    bw = (st.d >> 12) // tr
    bdim = x.shape[0]
    a = bdim // (2 * bw)
    xr = x.reshape(a, 2, bw, *x.shape[1:])
    lo, hi = xr[:, 0], xr[:, 1]
    t = (lo ^ hi) & m.reshape(a, bw, *m.shape[1:])
    return jnp.stack([lo ^ t, hi ^ t], axis=1).reshape(x.shape)


def _run_local_tile_major(x, arr2d, tr, specs, n, interpret, vma=None):
    """Pass B with tile-major masks: one big DMA per x-tile (all local
    stages' rows contiguous), double-buffered across grid steps — the
    next tile's block streams in while this tile computes.  See TILE_MAJOR
    for the measured rationale (big DMAs ride the chip's fast sequential
    path; many small per-stage copies collapse in slow-DMA windows)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nw = n // 32
    r = nw // LANES
    ntiles = max(r // tr, 1)
    block_rows = sum(_stage_rows(st, tr) for st in specs)
    x_view = x.reshape(r, LANES)
    x_spec = pl.BlockSpec((tr, LANES), lambda i: (i, 0))

    def guard(st, pid):
        if not _GUARDS:
            return None
        rows = _stage_rows(st, tr)
        if st.lo <= 0 and st.hi >= st.nwords:
            return None
        w0 = pid * rows * LANES
        return (w0 < st.hi) & (w0 + rows * LANES > st.lo)

    # bfs_tpu: hot
    def kernel(x_ref, m_hbm, o_ref, buf, sem):
        pid = pl.program_id(0)

        def dma(slot, t):
            return pltpu.make_async_copy(
                m_hbm.at[pl.ds(t * block_rows, block_rows), :],
                buf.at[slot],
                sem.at[slot],
            )

        @pl.when(pid == 0)
        def _():
            dma(0, 0).start()

        @pl.when(pid + 1 < ntiles)
        def _():
            dma((pid + 1) % 2, pid + 1).start()

        dma(pid % 2, pid).wait()
        xv = x_ref[...]
        slot = pid % 2
        for st in specs:
            rows = _stage_rows(st, tr)
            mv = buf[slot, pl.ds(st.offset // LANES, rows), :]
            g = guard(st, pid)
            if g is None:
                xv = _stage_local(xv, mv, st, interpret)
            else:
                xv = jnp.where(g, _stage_local(xv, mv, st, interpret), xv)
        o_ref[...] = xv

    if vma is None:
        out_shape = jax.ShapeDtypeStruct(x_view.shape, jnp.uint32)
    else:
        out_shape = jax.ShapeDtypeStruct(
            x_view.shape, jnp.uint32, vma=frozenset(vma)
        )
    out = pl.pallas_call(
        kernel,
        grid=(ntiles,),
        in_specs=[x_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=x_spec,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((2, block_rows, LANES), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
        name="relay_net_local",
    )(x_view, arr2d)
    return out.reshape(-1)


def _run_pass(x, arr2d, mode, tr, tt, specs, n, interpret, vma=None,
              lane64=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nw = n // 32
    r = nw // LANES
    b = r // tr

    if mode == "local_tm":
        return _run_local_tile_major(
            x, arr2d, tr, specs, n, interpret, vma
        )
    if mode == "local":
        grid = (r // tr,)
        x_view = x.reshape(r, LANES)
        x_spec = pl.BlockSpec((tr, LANES), lambda i: (i, 0))
        buf_rows = tr
        has_lane64 = any(_is_lane_compact(st) for st in specs)
        assert not has_lane64 or lane64 is not None

        def stage_rows(st):
            # lane-compact and row-compact stages both span tr//2 storage
            # rows of the 128-lane view; full stages span tr.
            return _stage_rows(st, tr)

        def dma(refs, mbufs, sem, slot, st, rows, pid):
            ref = refs[1] if _is_lane_compact(st) else refs[0]
            buf = mbufs[1] if _is_lane_compact(st) else mbufs[0]
            return pltpu.make_async_copy(
                ref.at[pl.ds(st.offset // LANES + pid * rows, rows), :],
                buf.at[slot, pl.ds(0, rows), :],
                sem.at[slot],
            )

        def guard(st, pid):
            if not _GUARDS:
                return None
            rows = stage_rows(st)
            if st.lo <= 0 and st.hi >= st.nwords:
                return None  # dense stage: unconditional (keeps DMA pipeline)
            w0 = pid * rows * LANES
            return (w0 < st.hi) & (w0 + rows * LANES > st.lo)

        def run_stage(xv, mbufs, slot, st):
            rows = stage_rows(st)
            buf = mbufs[1] if _is_lane_compact(st) else mbufs[0]
            return _stage_local(
                xv, buf[slot, pl.ds(0, rows), :], st, interpret
            )
    else:
        span = b // 2  # outer stages are always compact
        grid = (tr // tt,)
        x_view = x.reshape(b, tr, LANES)
        x_spec = pl.BlockSpec((b, tt, LANES), lambda j: (0, j, 0))
        buf_rows = span * tt
        has_lane64 = False

        def stage_rows(st):
            return span * tt

        def dma(refs, mbufs, sem, slot, st, rows, pid):
            return pltpu.make_async_copy(
                refs[0].at[pl.ds(st.offset // LANES + pid * rows, rows), :],
                mbufs[0].at[slot],
                sem.at[slot],
            )

        def guard(st, pid):
            del st, pid
            return None  # outer tiles always intersect live words

        def run_stage(xv, mbufs, slot, st):
            return _stage_outer(
                xv, mbufs[0][slot].reshape(span, tt, LANES), st, tr
            )

    depth = DMA_DEPTH

    def make_kernel(nrefs):
        # bfs_tpu: hot
        def kernel(x_ref, *rest):
            refs = rest[:nrefs]
            o_ref = rest[nrefs]
            scratch = rest[nrefs + 1 :]
            mbufs = scratch[:-1]
            sem = scratch[-1]
            pid = pl.program_id(0)
            xv = x_ref[...]
            n_st = len(specs)
            guards = [guard(st, pid) for st in specs]

            def start(si):
                st = specs[si]
                g = guards[si]
                if g is None:
                    dma(refs, mbufs, sem, si % depth, st, stage_rows(st),
                        pid).start()
                else:

                    @pl.when(g)
                    def _():
                        dma(refs, mbufs, sem, si % depth, st, stage_rows(st),
                            pid).start()

            # Keep depth-1 mask copies in flight: stage si+depth-1's DMA is
            # issued as stage si begins.  Slot si%depth is reclaimed at issue
            # time si+depth-1, whose program point is after stage si's
            # compute consumed it.
            for w in range(min(depth - 1, n_st)):
                start(w)
            for si, st in enumerate(specs):
                if si + depth - 1 < n_st:
                    start(si + depth - 1)
                g = guards[si]
                if g is None:
                    dma(refs, mbufs, sem, si % depth, st, stage_rows(st),
                        pid).wait()
                    xv = run_stage(xv, mbufs, si % depth, st)
                else:

                    @pl.when(g)
                    def _():
                        dma(refs, mbufs, sem, si % depth, st, stage_rows(st),
                            pid).wait()

                    xv = jnp.where(g, run_stage(xv, mbufs, si % depth, st), xv)
            o_ref[...] = xv

        return kernel

    if vma is None:
        out_shape = jax.ShapeDtypeStruct(x_view.shape, jnp.uint32)
    else:
        # Inside shard_map with varying-mesh-axes checking, a pallas output
        # must declare which mesh axes it varies over (parallel/sharded.py
        # passes the graph axis).
        out_shape = jax.ShapeDtypeStruct(
            x_view.shape, jnp.uint32, vma=frozenset(vma)
        )
    operands = [x_view, arr2d]
    in_specs = [x_spec, pl.BlockSpec(memory_space=pl.ANY)]
    scratch = [pltpu.VMEM((depth, buf_rows, LANES), jnp.uint32)]
    if has_lane64:
        operands.append(lane64)
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        scratch.append(pltpu.VMEM((depth, tr // 2, LANES), jnp.uint32))
    scratch.append(pltpu.SemaphoreType.DMA((depth,)))
    out = pl.pallas_call(
        make_kernel(len(operands) - 1),
        grid=grid,
        in_specs=in_specs,
        out_specs=x_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name=f"relay_net_{mode}",
    )(*operands)
    return out.reshape(-1)


# ---------------------------------------------------------------------------
# Element-major mode: x carries one uint32 PER ELEMENT (bit t = tree t of a
# 32-tree batch, ops/relay_elem.py).  Stage masks are re-packed VERTICALLY
# host-side (:func:`prepare_elem_pass_masks`): word (R, l) holds bits for
# elements (32R + j, l) — so the in-kernel bit->select expansion is one
# sublane broadcast plus a per-row variable shift, no lane shuffles.

#: element-mode local-pass tile rows: (G, TILE_ROWS_E, 128) uint32
#: elements.  Mosaic unrolls a stage over every vreg of the tile, so cold
#: compile time grows with the tile: a 8192-row local pass did not compile
#: for a v5e within 390 s, 256 rows compiles in seconds (PERF.md, PR 21).
TILE_ROWS_E = 256
#: outer-pass chunk rows: an outer block is (G, 2^bits, OUTER_TT_E, 128).
OUTER_TT_E = 64
#: most outer (block-distance) stages one outer pass fuses: its block is
#: (G, 2^OUTER_BITS_E, OUTER_TT_E, 128) = 8 MB at G=1, the word mode's
#: outer block at s22.  Outer stages beyond this window take further
#: passes, each strided over the block axis, so VMEM stays bounded at any
#: net size (tree groups still run through the passes one at a time).
OUTER_BITS_E = 8


def elem_pass_static(
    table: tuple[StageSpec, ...], n: int,
    tile_rows: int = TILE_ROWS_E, outer_tt: int = OUTER_TT_E,
    outer_bits: int = OUTER_BITS_E,
):
    """Pass split for element-major mode: ``((mode, tr, tt, specs, m),
    ...)`` in execution order.  The local pass fuses the stages with
    d < tile_rows*128 on (tr, 128) tiles (m = 1).  The outer stages above
    it are grouped, in table order, into windows of at most ``outer_bits``
    consecutive block bits; an outer pass views x as (hi, m, tr, 128)
    with m = 2^window blocks of tr rows (tr = tile rows * 2^lowest bit)
    and fuses the window on (m, tt, 128) blocks.  Mirrors
    :func:`prepare_elem_pass_masks`."""
    r = n // LANES
    t0 = min(tile_rows, max(r, 1))
    local = [i for i, st in enumerate(table) if st.d < t0 * LANES]
    assert local and local == list(
        range(local[0], local[-1] + 1)
    ), "local stages must be consecutive"
    lo, hi = local[0], local[-1] + 1

    def block_bit(st):
        return (st.d // (t0 * LANES)).bit_length() - 1

    def specs_of(idx):
        specs, off = [], 0
        for i in idx:
            st = table[i]
            specs.append(st._replace(offset=off, lo=0, hi=st.nwords))
            off += st.nwords
        return tuple(specs)

    def outer(idx):
        out, run = [], []
        for i in idx:
            bits = [block_bit(table[j]) for j in run + [i]]
            if run and max(bits) - min(bits) >= outer_bits:
                out.append(run)
                run = []
            run.append(i)
        out.append(run)
        passes = []
        for run in out:
            bits = [block_bit(table[j]) for j in run]
            tr = t0 << min(bits)
            m = 1 << (max(bits) - min(bits) + 1)
            tt = min(outer_tt, tr)
            passes.append(("outer", tr, tt, specs_of(run), m))
        return passes

    ps = outer(range(lo)) if lo > 0 else []
    ps.append(("local", t0, min(outer_tt, t0), specs_of(range(lo, hi)), 1))
    if hi < len(table):
        ps += outer(range(hi, len(table)))
    return tuple(ps)


def _vertical_repack(words: np.ndarray, nelem: int) -> np.ndarray:
    """Standard-packed stage words -> vertical packing: output word (R, l)
    holds bits of elements (32R + j, l), j in [0, 32)."""
    bits = np.unpackbits(
        words.view(np.uint8), bitorder="little"
    ).reshape(-1, 32, LANES)
    by = np.packbits(bits, axis=1, bitorder="little")  # (R, 4, LANES) bytes
    return (
        np.ascontiguousarray(by.transpose(0, 2, 1))  # word bytes contiguous
        .view(np.uint32)
        .reshape(-1)
    )


def prepare_elem_pass_masks(
    masks_flat: np.ndarray, table: tuple[StageSpec, ...], n: int,
    tile_rows: int = TILE_ROWS_E, outer_tt: int = OUTER_TT_E,
    outer_bits: int = OUTER_BITS_E,
):
    """Host-side (cached by engines): per-pass vertically-packed mask arrays
    for element-major mode.  An outer stage's pairs index (hi, m/2, tr)
    rows; they are re-chunked to (hi, tr/tt, m/2, tt) order so each grid
    step's mask block is one contiguous DMA, mirroring
    :func:`prepare_pass_masks`."""
    r = n // LANES
    arrays = []
    gi = 0
    for mode, tr, tt, specs, m in elem_pass_static(
        table, n, tile_rows, outer_tt, outer_bits
    ):
        parts = []
        for _ in specs:
            st = table[gi]
            gi += 1
            w = masks_flat[st.offset : st.offset + st.nwords]
            wv = _vertical_repack(w, st.nwords * 32)
            if mode == "outer":  # outer stages are pair-compact
                nhi = r // (tr * m)
                wv = (
                    wv.reshape(nhi, m // 2, tr // tt, tt // 32, LANES)
                    .swapaxes(1, 2)
                    .reshape(-1)
                )
            parts.append(wv)
        arrays.append(
            np.concatenate(parts).reshape(-1, LANES)
            if parts
            else np.zeros((0, LANES), np.uint32)
        )
    return arrays


def _expand_vertical(mv, rows: int, interpret: bool):
    """Vertically-packed mask words (rows//32, LANES) -> per-element select
    (rows, LANES) uint32 0/~0."""
    rep = jnp.repeat(mv, 32, axis=0)
    ri = jax.lax.broadcasted_iota(jnp.uint32, (rows, LANES), 0) & 31
    return jnp.uint32(0) - ((rep >> ri) & 1)


def _elem_stage_local(x, sel_rows, st: StageSpec, interpret: bool):
    """One butterfly stage on an element tile x: (G, tr, LANES).
    ``sel_rows``: expanded select for the stage's stored rows."""
    d = st.d
    g = x.shape[0]
    tr = x.shape[1]
    if d < LANES:  # lane butterfly: select at lower pair lanes, roll-mirror
        idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 2)
        has = (idx & d) != 0
        sel = sel_rows[None, :, :]
        partner = jnp.where(
            has, _kroll(x, d, 2, interpret), _kroll(x, -d, 2, interpret)
        )
        m_both = jnp.where(has, _kroll(sel, d, 2, interpret), sel)
        return x ^ ((x ^ partner) & m_both)
    rw = d // LANES  # row butterfly
    if st.compact:
        a = tr // (2 * rw)
        xr = x.reshape(g, a, 2, rw, LANES)
        lo, hi = xr[:, :, 0], xr[:, :, 1]
        t = (lo ^ hi) & sel_rows.reshape(1, a, rw, LANES)
        return jnp.stack([lo ^ t, hi ^ t], axis=2).reshape(x.shape)
    a = tr // (2 * rw)
    xr = x.reshape(g, a, 2, rw, LANES)
    lo, hi = xr[:, :, 0], xr[:, :, 1]
    sl = sel_rows.reshape(a, 2, rw, LANES)[:, 0]
    t = (lo ^ hi) & sl.reshape(1, a, rw, LANES)
    return jnp.stack([lo ^ t, hi ^ t], axis=2).reshape(x.shape)


def _elem_stage_outer(x, sel, st: StageSpec, tr: int):
    """Outer-block butterfly: x (G, B, tt, LANES); sel (B/2, tt, LANES)."""
    bw = (st.d // LANES) // tr
    bdim = x.shape[1]
    a = bdim // (2 * bw)
    xr = x.reshape(x.shape[0], a, 2, bw, *x.shape[2:])
    lo, hi = xr[:, :, 0], xr[:, :, 1]
    t = (lo ^ hi) & sel.reshape(1, a, bw, *sel.shape[1:])
    return jnp.stack([lo ^ t, hi ^ t], axis=2).reshape(x.shape)


def _run_elem_pass(x, arr2d, mode, tr, tt, specs, m, n, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g = x.shape[0]
    r = n // LANES

    if mode == "local":
        grid = (r // tr,)
        x_view = x.reshape(g, r, LANES)
        x_spec = pl.BlockSpec((g, tr, LANES), lambda i: (0, i, 0))

        def stage_mrows(st):
            rows = tr // 2 if st.compact else tr
            return rows // 32

        def dma(m_hbm, mbuf, sem, slot, st, mrows, pid):
            return pltpu.make_async_copy(
                m_hbm.at[pl.ds(st.offset // LANES + pid * mrows, mrows), :],
                mbuf.at[slot, pl.ds(0, mrows), :],
                sem.at[slot],
            )

        def run_stage(xv, mbuf, slot, st):
            mrows = stage_mrows(st)
            sel = _expand_vertical(
                mbuf[slot, pl.ds(0, mrows), :], mrows * 32, interpret
            )
            return _elem_stage_local(xv, sel, st, interpret)

        def program_id():
            return pl.program_id(0)

        buf_rows = tr // 32
    else:
        # x as (hi, m, tr, 128): the pass's block bits are the m axis; the
        # hi blocks above and the tr rows below are batch.
        span = m // 2
        nhi = r // (tr * m)
        grid = (nhi, tr // tt)
        x_view = x.reshape(g, nhi, m, tr, LANES)
        x_spec = pl.BlockSpec(
            (g, 1, m, tt, LANES), lambda h, j: (0, h, 0, j, 0)
        )

        def stage_mrows(st):
            return span * (tt // 32)

        def dma(m_hbm, mbuf, sem, slot, st, mrows, pid):
            return pltpu.make_async_copy(
                m_hbm.at[pl.ds(st.offset // LANES + pid * mrows, mrows), :],
                mbuf.at[slot],
                sem.at[slot],
            )

        def run_stage(xv, mbuf, slot, st):
            mrows = stage_mrows(st)
            sel = _expand_vertical(
                mbuf[slot].reshape(mrows, LANES), mrows * 32, interpret
            ).reshape(span, tt, LANES)
            return _elem_stage_outer(
                xv.reshape(g, m, tt, LANES), sel, st, tr
            ).reshape(xv.shape)

        def program_id():
            return pl.program_id(0) * (tr // tt) + pl.program_id(1)

        buf_rows = span * (tt // 32)

    depth = DMA_DEPTH

    # bfs_tpu: hot
    def kernel(x_ref, m_hbm, o_ref, mbuf, sem):
        pid = program_id()
        xv = x_ref[...]
        n_st = len(specs)
        for w in range(min(depth - 1, n_st)):
            dma(m_hbm, mbuf, sem, w % depth, specs[w], stage_mrows(specs[w]),
                pid).start()
        for si, st in enumerate(specs):
            if si + depth - 1 < n_st:
                nst = specs[si + depth - 1]
                dma(m_hbm, mbuf, sem, (si + depth - 1) % depth, nst,
                    stage_mrows(nst), pid).start()
            dma(m_hbm, mbuf, sem, si % depth, st, stage_mrows(st), pid).wait()
            xv = run_stage(xv, mbuf, si % depth, st)
        o_ref[...] = xv

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[x_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(x_view.shape, jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM((depth, buf_rows, LANES), jnp.uint32),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
        interpret=interpret,
        name="relay_net_elem",
    )(x_view, arr2d)
    return out.reshape(g, n)


def apply_benes_elem_fused(
    x: jax.Array, pass_arrays, pass_static_info, n: int,
    interpret: bool = False,
) -> jax.Array:
    """Element-major routed Beneš network in fused passes: x uint32[G, n]."""
    for (mode, tr, tt, specs, m), arr in zip(pass_static_info, pass_arrays):
        x = _run_elem_pass(x, arr, mode, tr, tt, specs, m, n, interpret)
    return x


def elem_superstep_tpu_factory(static, plane_offsets, pt: int):
    """Element-major superstep for real TPUs: the two Beneš networks run as
    fused element-major passes (x VMEM-resident, vertically-packed masks
    streamed once per superstep FOR ALL 32*G trees); broadcast, row-min
    tournament and the bit-sliced apply stay in XLA."""
    (vr, vperm_size, vperm_table, out_classes, out_space, net_table,
     net_size, in_classes) = static
    from . import relay_elem as RE

    vp_ok = pallas_net_ok(vperm_size)
    net_ok = pallas_net_ok(net_size)
    vp_static = elem_pass_static(vperm_table, vperm_size) if vp_ok else None
    net_static = elem_pass_static(net_table, net_size) if net_ok else None

    def superstep(st, vperm_m, net_m, valid_words):
        g = st.frontier.shape[0]
        fw = jnp.concatenate(
            [st.frontier, jnp.zeros((g, vperm_size - vr), jnp.uint32)],
            axis=1,
        )
        if vp_ok:  # groups run sequentially: element tiles are VMEM-hungry
            y = jnp.concatenate([
                apply_benes_elem_fused(
                    fw[gi : gi + 1], vperm_m, vp_static, vperm_size
                )
                for gi in range(g)
            ])
        else:
            y = RE.apply_benes_elem(fw, vperm_m, vperm_table, vperm_size)
        l2 = RE.broadcast_l2_elem(y, out_classes, net_size)
        if net_ok:
            l1 = jnp.concatenate([
                apply_benes_elem_fused(
                    l2[gi : gi + 1], net_m, net_static, net_size
                )
                for gi in range(g)
            ])
        else:
            l1 = RE.apply_benes_elem(l2, net_m, net_table, net_size)
        found, rp_new = RE.rowmin_elem(
            l1, valid_words, in_classes, vr, plane_offsets, pt
        )
        newly = found & ~st.visited
        visited = st.visited | newly
        new_level = st.level + 1
        lev = new_level.astype(jnp.uint32)
        dist_planes = jnp.stack(
            [
                jnp.where(
                    (lev >> b) & 1, st.dist_planes[b] | newly,
                    st.dist_planes[b],
                )
                for b in range(RE.DIST_PLANES)
            ]
        )
        rp_mask_parts = []
        for cs in sorted(in_classes, key=lambda c: c.va):
            _, nb = plane_offsets[cs.va]
            if nb:
                seg = jax.lax.slice_in_dim(newly, cs.va, cs.vb, axis=1)
                rp_mask_parts.append(jnp.tile(seg, (1, nb)))
        rp_mask = (
            jnp.concatenate(rp_mask_parts, axis=1)
            if rp_mask_parts
            else jnp.zeros_like(st.rank_planes)
        )
        rank_planes = st.rank_planes | (rp_new & rp_mask)
        return RE.ElemState(
            visited=visited, frontier=newly, dist_planes=dist_planes,
            rank_planes=rank_planes, level=new_level,
            changed=(newly != 0).any(),
        )

    return superstep


# ---------------------------------------------------------------------------
# Per-phase kernels beyond the Beneš appliers (ISSUE 7 tentpole b): the two
# next-largest ledger phases after net-apply — the masked row-min tournament
# and the packed lexicographic-min state update — as fused Pallas kernels,
# each bit-exact against its XLA twin (ops/relay.rowmin_ranks /
# apply_relay_candidates_packed) and selected PER PHASE by measurement
# (profiling.probe_phase_kernels feeds the engine's phase_selection), never
# by default.  Off-TPU they run in interpret mode — measured for the ledger
# verdict and exercised for parity in tests, but interpret overheads mean
# XLA wins the selection there.


def pallas_interpret() -> bool:
    """Interpret-mode flag for the per-phase kernels: real Mosaic on TPU
    backends, the Pallas interpreter everywhere else (parity tests + the
    ledger's measured-arm probes on CPU)."""
    return jax.default_backend() != "tpu"


def _pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


#: VMEM word budget for one row-min tile ([width, chunk] uint32 x2 operands).
ROWMIN_TILE_WORDS = 1 << 19


def _rowmin_chunk(width: int, cw: int) -> int:
    """Lane-chunk for one class's [width, cw] tournament tile: the whole
    row span when it fits the VMEM budget, else the largest 128-lane
    multiple dividing ``cw`` under it (a block's lane extent must be the
    full span or a multiple of 128); 0 when nothing fits (class falls
    back to XLA)."""
    p2 = 1 << max((width - 1).bit_length(), 0)
    limit = ROWMIN_TILE_WORDS // max(p2, 1)
    if limit < 1:
        return 0
    if cw <= limit:
        return cw
    aligned = [d for d in range(LANES, limit + 1, LANES) if cw % d == 0]
    return aligned[-1] if aligned else 0


def rowmin_class_ok(cs) -> bool:
    """Is one class eligible for the fused tournament kernel?  Rank-major
    with at least two rows (the kernel zero-pads rows to the next power
    of two, mirroring the XLA tournament) and a chunk under the VMEM
    budget must exist."""
    return (
        not cs.vertex_major
        and cs.width >= 2
        and _rowmin_chunk(cs.width, cs.count // 32) > 0
    )


def _class_tournament_call(x2d, v2d, width: int, cw: int, interpret: bool):
    """Masked min-row-index tournament over one class's [width, cw] word
    view: returns uint32[1 + log2(width), cw] — row 0 the found words,
    rows 1.. the rank bit-plane words low..high, bit-exact with
    ops/relay._word_tournament on ``x & v``.  The grid streams lane
    chunks; each instance holds one [width, chunk] tile x2 in VMEM and
    runs the log2(width) merge rounds register-resident — the XLA path
    round-trips every round through HBM."""
    from jax.experimental import pallas as pl

    nb = max(width - 1, 1).bit_length() if width > 1 else 0
    chunk = _rowmin_chunk(width, cw)

    # bfs_tpu: hot
    def kernel(x_ref, v_ref, o_ref):
        f = x_ref[...] & v_ref[...]
        rows = f.shape[0]
        p2 = 1 << max((rows - 1).bit_length(), 0)
        if p2 != rows:
            # Zero-pad rows to the power of two the log reduce halves —
            # exactly the XLA tournament's padding (zero words never win).
            f = jnp.concatenate(
                [f, jnp.zeros((p2 - rows, f.shape[-1]), jnp.uint32)], axis=0
            )
            rows = p2
        planes: list = []
        while rows > 1:
            fr = f.reshape(rows // 2, 2, f.shape[-1])
            fa, fb = fr[:, 0, :], fr[:, 1, :]
            new_planes = []
            for pl_w in planes:
                pr = pl_w.reshape(rows // 2, 2, pl_w.shape[-1])
                new_planes.append(pr[:, 0, :] | (pr[:, 1, :] & ~fa))
            new_planes.append(fb & ~fa)
            planes = new_planes
            f = fa | fb
            rows //= 2
        o_ref[...] = jnp.concatenate([f] + planes, axis=0)

    in_spec = pl.BlockSpec((width, chunk), lambda j: (0, j))
    return pl.pallas_call(
        kernel,
        grid=(cw // chunk,),
        in_specs=[in_spec, in_spec],
        out_specs=pl.BlockSpec((nb + 1, chunk), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((nb + 1, cw), jnp.uint32),
        interpret=interpret,
        name="relay_rowmin",
    )(x2d, v2d)


# bfs_tpu: hot traced
def rowmin_ranks_pallas(
    l1words: jax.Array, valid_words: jax.Array, in_classes, vr: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Pallas flavor of :func:`bfs_tpu.ops.relay.rowmin_ranks`: min active
    RANK per relabeled vertex (uint32, PACKED_SENTINEL where none), with
    eligible rank-major classes' tournaments fused into one VMEM-resident
    kernel per class and the masking (``l1 & valid``) applied in-kernel —
    the two net-sized operands stream through VMEM exactly once.
    Ineligible classes (vertex-major, non-pow2 width, unaligned chunk)
    take the XLA tournament, so the output is bit-exact with the XLA path
    for every layout."""
    from . import relay as R
    from .packed import PACKED_SENTINEL

    if interpret is None:
        interpret = pallas_interpret()
    cands = []
    covered = 0
    for cs in sorted(in_classes, key=lambda c: c.va):
        assert cs.va == covered, "in_classes must tile the vertex space"
        if rowmin_class_ok(cs):
            a, b = cs.sa // 32, cs.sb // 32
            cw = cs.count // 32
            x2d = jax.lax.slice_in_dim(l1words, a, b).reshape(cs.width, cw)
            v2d = jax.lax.slice_in_dim(valid_words, a, b).reshape(
                cs.width, cw
            )
            out = _class_tournament_call(
                x2d, v2d, cs.width, cw, interpret
            )
            found = R.unpack_std(out[0], cs.count) != 0
            rank = jnp.zeros(cs.count, jnp.int32)
            for j in range(out.shape[0] - 1):
                rank = rank | (
                    R.unpack_std(out[j + 1], cs.count).astype(jnp.int32)
                    << j
                )
        else:
            found, rank = R._class_found_rank(
                R._masked_class_words(l1words, valid_words, cs), cs
            )
        cands.append(
            jnp.where(found, rank.astype(jnp.uint32), PACKED_SENTINEL)
        )
        covered = cs.vb
    if covered < vr:
        cands.append(jnp.full(vr - covered, PACKED_SENTINEL, jnp.uint32))
    return jnp.concatenate(cands)


#: State-update view: packed words as [vr/128, 128] in tiles of ``tr``
#: rows (tr % 32 == 0), so vr pads to a multiple of 32*128 elements.
_UPDATE_ALIGN = 32 * LANES


def _update_tile_rows(rows: int) -> int:
    for tr in (2048, 1024, 512, 256, 128, 64, 32):
        if rows % tr == 0:
            return tr
    return 0


def _umin(a, b):
    """Unsigned 32-bit min through a signed compare (Mosaic has no
    unsigned min): flipping the sign bit maps unsigned order onto signed."""
    flip = jnp.uint32(0x80000000)
    sa = jax.lax.bitcast_convert_type(a ^ flip, jnp.int32)
    sb = jax.lax.bitcast_convert_type(b ^ flip, jnp.int32)
    return jnp.where(sa <= sb, a, b)


def _apply_packed_kernel_factory(tr: int, interpret: bool):
    # bfs_tpu: hot
    def kernel(x_ref, c_ref, o_ref, f_ref):
        pk = x_ref[...]
        pk2 = _umin(pk, c_ref[...])  # THE lexicographic min
        newly = (pk2 != pk).astype(jnp.uint32)
        lane = jax.lax.broadcasted_iota(jnp.uint32, pk.shape, 1)
        lmod = lane & 31
        # Standard packing in-register: t0 = bit << (lane%32), then a
        # 5-step guarded prefix-OR within each 32-lane group leaves the
        # group's packed word at its lane-0 slot.  Mosaic has no
        # stride-32 lane gather, so the words leave at those slots and the
        # caller strides them out (flat word row*4 + lane/32 = the
        # standard ``e >> 5`` word order).
        t = newly << lmod
        for k in (1, 2, 4, 8, 16):
            rolled = _kroll(t, -k, 1, interpret)
            t = t | jnp.where(lmod + k < 32, rolled, jnp.uint32(0))
        f_ref[...] = t
        o_ref[...] = pk2

    return kernel


# bfs_tpu: hot traced
def apply_relay_candidates_packed_pallas(
    state, rank_or_sent: jax.Array, interpret: bool | None = None,
):
    """Pallas flavor of
    :func:`bfs_tpu.ops.relay.apply_relay_candidates_packed`: the packed
    lexicographic-min state update with the frontier-word repack fused
    into the same kernel — the packed carry and candidate words stream
    through VMEM once and the newly-bits never materialize as a V-sized
    bool array in HBM (the XLA path's ``pack_std`` reads them back).
    Bit-exact with the XLA twin; the carry tail (level, changed) follows
    the same contract."""
    from jax.experimental import pallas as pl

    from .packed import PACKED_SENTINEL, level_word
    from .relay import PackedRelayState

    if interpret is None:
        interpret = pallas_interpret()
    cand = rank_or_sent | level_word(state.level + 1)
    vr = state.packed.shape[0]
    vrp = ((vr + _UPDATE_ALIGN - 1) // _UPDATE_ALIGN) * _UPDATE_ALIGN
    pk = state.packed
    if vrp != vr:
        pad = jnp.full(vrp - vr, PACKED_SENTINEL, jnp.uint32)
        pk = jnp.concatenate([pk, pad])
        cand = jnp.concatenate([cand, pad])
    rows = vrp // LANES
    tr = _update_tile_rows(rows)
    x_spec = pl.BlockSpec((tr, LANES), lambda i: (i, 0))
    pk2, fw = pl.pallas_call(
        _apply_packed_kernel_factory(tr, interpret),
        grid=(rows // tr,),
        in_specs=[x_spec, x_spec],
        out_specs=(x_spec, x_spec),
        out_shape=(
            jax.ShapeDtypeStruct((rows, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.uint32),
        ),
        interpret=interpret,
        name="relay_apply_packed",
    )(pk.reshape(rows, LANES), cand.reshape(rows, LANES))
    packed2 = pk2.reshape(-1)[:vr]
    fwords = fw[:, ::32].reshape(-1)[: vr // 32]
    return PackedRelayState(
        packed2, fwords, state.level + 1, (fwords != jnp.uint32(0)).any()
    )


def apply_benes_fused(
    words: jax.Array,
    pass_arrays,  # device arrays in prepare_pass_masks order
    pass_static,  # tuple of (mode, tr, tt, specs) in the same order
    n: int,
    interpret: bool = False,
    vma=None,  # mesh axes the result varies over (shard_map callers)
) -> jax.Array:
    """The full routed Beneš network in at most three fused Pallas passes.
    The local pass consumes TWO arrays (main + lane64 side array) when any
    of its stages is lane-compacted — prepare_pass_masks emits them
    adjacently."""
    x = words
    ai = 0
    for mode, tr, tt, specs in pass_static:
        arr = pass_arrays[ai]
        ai += 1
        lane64 = None
        if mode == "local" and any(_is_lane_compact(st) for st in specs):
            lane64 = pass_arrays[ai]
            ai += 1
        x = _run_pass(x, arr, mode, tr, tt, specs, n, interpret, vma, lane64)
    return x
