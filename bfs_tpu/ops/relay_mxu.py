"""MXU-native frontier expansion: BFS as bit-packed masked matmul (ISSUE 15).

The third expansion arm next to the sparse gather (push) and the Beneš
relay pipeline (the gather-free pull): dense frontier levels expand as
tiled products of the frontier bitmap against the bit-packed 128x128
adjacency tiles of :mod:`bfs_tpu.graph.adj_tiles` — the BLEST /
graph-traversal-on-tensor-cores formulation, shaped for the TPU MXU.

Per (frontier row-block, adjacency tile) the kernel computes the
CONTRIBUTION MASKS as one matmul:

    FW[g, u]  = frontier_bit(u) * 2^(u mod 16)   for u // 16 == g   (8x128)
    M = FW @ A_tile                                                 (8x128)

``A_tile`` is the tile unpacked to 0/1 f32.  Each group sums at most 16
distinct powers of two < 2^16, so the f32 accumulation is EXACT and
``M[g, v]`` is literally the 16-bit bitmask of group-``g`` sources that
reach destination ``v`` — the matmul does the whole neighborhood
intersection.  The epilogue reduces each mask to the minimum ORIGINAL
source id (``keys2d``) and min-accumulates across the column's tiles:

    cand[v] = min over contributing frontier sources u of orig_id(u)

which is the canonical min-parent candidate every engine shares, emitted
as ``uint32`` with ``PACKED_SENTINEL`` where no source contributes — the
exact operand :func:`bfs_tpu.ops.relay.apply_relay_candidates_packed`
merges (the parent field carries the ORIGINAL id; models/bfs.py's mxu
finish decodes it without the rank->slot reconstruction).

Early-out: a tile whose 128-bit frontier block is all zero is SKIPPED
before its 2 KB DMA is even issued (``pl.when`` on the 4 preloaded
frontier words), so sparse-frontier supersteps touch no adjacency bytes —
though the direction optimizer routes those levels to the push arm anyway.

:func:`expand_frontier_mxu_xla` is the bit-identical XLA twin (the PAL005
parity oracle diffs raw bytes against it; it is also the shipping arm on
CPU backends and under ``vmap`` in the batched multi-source program —
min over uint32 keys is associative/commutative and exact, so any
evaluation order produces identical bytes).

Knobs::

    BFS_TPU_EXPANSION    auto | gather | mxu   (default auto)
    BFS_TPU_MXU_KERNEL   auto | pallas | xla   (default auto: pallas on
                         TPU backends, the XLA twin elsewhere)
    BFS_TPU_MXU_TILE_GB  float tile-storage budget for auto/mxu (default 4)
    BFS_TPU_TILES        resident | stream | auto   (default resident):
                         where the tile layout LIVES — device-resident, or
                         paged per-superblock from host RAM by demand
                         (bfs_tpu/stream, ISSUE 18); auto streams exactly
                         when the layout exceeds the stream cache budget
    BFS_TPU_STREAM_CACHE_GB  float HBM superblock-cache budget for the
                         streamed arm (default 1)
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from .. import knobs
from ..graph.adj_tiles import SB_TILES, SB_VERTS, TILE, TILE_WORDS

__all__ = [
    "EXPANSION_MODES",
    "TILES_MODES",
    "resolve_expansion",
    "resolve_mxu_kernel",
    "resolve_tiles_mode",
    "tiles_budget_bytes",
    "stream_cache_budget_bytes",
    "expand_frontier_mxu",
    "expand_frontier_mxu_xla",
    "mxu_device_operands",
    "mxu_operand_arrays",
    "mxu_superstep_packed",
    "mxu_superstep",
]

SENT = np.uint32(0xFFFFFFFF)  # == ops.packed.PACKED_SENTINEL
GROUPS = TILE // 16  # 8 weight groups of 16 rows; 2^0..2^15 exact in f32

EXPANSION_MODES = ("auto", "gather", "mxu")


def resolve_expansion(mode: str | None = None) -> str:
    """``BFS_TPU_EXPANSION`` (an explicit argument wins).  Raises on
    unknown modes — a typo'd knob must never silently change what a
    capture measured."""
    if mode is None:
        mode = knobs.get("BFS_TPU_EXPANSION")
    if mode not in EXPANSION_MODES:
        raise ValueError(
            f"unknown expansion {mode!r}; use 'auto', 'gather' or 'mxu'"
        )
    return mode


def resolve_mxu_kernel(kernel: str | None = None) -> str:
    """Which implementation the mxu arm's DENSE superstep compiles:
    ``pallas`` (the fused kernel; interpret-mode off-TPU — parity tests
    only, never a shipping loop) or ``xla`` (the twin).  ``auto`` follows
    the backend like every other per-phase kernel here."""
    if kernel is None:
        kernel = knobs.get("BFS_TPU_MXU_KERNEL")
    if kernel not in ("auto", "pallas", "xla"):
        raise ValueError(
            f"unknown mxu kernel {kernel!r}; use 'auto', 'pallas' or 'xla'"
        )
    if kernel == "auto":
        try:
            return "pallas" if jax.default_backend() == "tpu" else "xla"
        except Exception:  # pragma: no cover - backend init failure
            return "xla"
    return kernel


TILES_MODES = ("resident", "stream", "auto")


def resolve_tiles_mode(mode: str | None = None) -> str:
    """``BFS_TPU_TILES`` (an explicit argument wins): where the mxu arm's
    tile layout lives.  ``resident`` ships the whole layout to HBM at
    engine init (the ISSUE 15 behavior and the default); ``stream`` pages
    column superblocks from a pinned host store on frontier demand
    (bfs_tpu/stream, ISSUE 18); ``auto`` streams exactly when the layout
    exceeds :func:`stream_cache_budget_bytes` — the layout fits, keep it
    resident.  Raises on unknown modes, same contract as
    :func:`resolve_expansion`."""
    if mode is None:
        mode = knobs.get("BFS_TPU_TILES")
    if mode not in TILES_MODES:
        raise ValueError(
            f"unknown tiles mode {mode!r}; use 'resident', 'stream' or "
            "'auto'"
        )
    return mode


def stream_cache_budget_bytes() -> int:
    """HBM budget for the streamed arm's superblock cache
    (``BFS_TPU_STREAM_CACHE_GB``, default 1 GB) — the working-set ceiling
    the LRU accounts against, NOT a hard allocator limit (in-flight
    expands keep their operand references alive past eviction, exactly
    like the serve registry's resident map)."""
    return int(knobs.get("BFS_TPU_STREAM_CACHE_GB") * (1 << 30))


def tiles_budget_bytes() -> int:
    """Tile-storage ceiling for building the mxu layout
    (``BFS_TPU_MXU_TILE_GB``, default 4 GB): a scale-free tail can
    degrade toward one 2 KB tile per edge, and the arm must never OOM a
    host just by being probed."""
    return int(knobs.get("BFS_TPU_MXU_TILE_GB") * (1 << 30))


#: Tiles per twin ``lax.map`` slab; shipped tile counts are padded to it.
TWIN_CHUNK = 256

#: Tiles whose (row, column) indices are DMA'd into SMEM at a time: the
#: whole [ntp] index arrays outgrow the 1 MiB SMEM past ~100k tiles.
_IDX_CHUNK = 1024

#: Tiles per frontier-word DMA block: the [ntp, 4] per-tile frontier
#: words travel lane-dense as [ntp/32, 128] rows, DMA'd 8 rows at a time.
_F_ROWS = 8
_F_BLOCK = _F_ROWS * TILE // TILE_WORDS  # 256 tiles per (8, 128) block


def mxu_operand_arrays(tiles, row_idx, col_id, sb_indptr, keys2d, *,
                       rtp: int, vtp: int) -> tuple:
    """Host-side, once per layout: the tile-operand tuple ``(tiles_wm,
    row_idx, col_id, sb_indptr, keys_b, idx)`` in the layout both the
    kernel and the twin read, so no call re-lays out the tiles.

    - ``tiles_wm``: tiles word-major in pairs, ``[ntp/2, 8, 128]`` (row
      ``4*(t&1)+j`` = word ``j`` of tile ``t`` over its 128 source rows),
      padded with inert tiles to a multiple of :data:`TWIN_CHUNK`;
    - ``row_idx`` / ``col_id``: per tile, padded likewise (the frontier's
      zero pad block / the dropped overflow column segment);
    - ``keys_b``: the key table in 8-row blocks, ``[-1, 8, 128]``;
    - ``idx``: (row, superblock-local column) per tile in SMEM-sized
      chunks, ``[-1, 2, _IDX_CHUNK]``."""
    k = (-tiles.shape[0]) % TWIN_CHUNK
    if k:
        tiles = np.concatenate(
            [tiles, np.zeros((k, TILE, TILE_WORDS), np.uint32)]
        )
        row_idx = np.concatenate([row_idx, np.full(k, rtp // TILE, np.int32)])
        col_id = np.concatenate([col_id, np.full(k, vtp // TILE, np.int32)])
    tiles_wm = np.ascontiguousarray(np.swapaxes(tiles, 1, 2)).reshape(
        -1, 2 * TILE_WORDS, TILE
    )
    keys = np.asarray(keys2d, np.uint32)
    keys = np.concatenate(
        [keys, np.zeros(((-keys.shape[0]) % 8, TILE), np.uint32)]
    ).reshape(-1, 8, TILE)
    idx = np.stack([row_idx, col_id % SB_TILES], axis=1).astype(np.int32)
    idx = np.concatenate(
        [idx, np.zeros(((-idx.shape[0]) % _IDX_CHUNK, 2), np.int32)]
    )
    idx = np.ascontiguousarray(
        np.swapaxes(idx.reshape(-1, _IDX_CHUNK, 2), 1, 2)
    )
    return (tiles_wm, np.asarray(row_idx, np.int32),
            np.asarray(col_id, np.int32), np.asarray(sb_indptr, np.int32),
            keys, idx)


def mxu_device_operands(at) -> tuple:
    """Ship an :class:`~bfs_tpu.graph.adj_tiles.AdjTiles` layout as the
    fused programs' tile-operand tuple (:func:`mxu_operand_arrays`) — the
    one pytree both the kernel and the twin consume (static geometry
    travels separately via :func:`mxu_static`)."""
    return tuple(
        jnp.asarray(a)
        for a in mxu_operand_arrays(
            at.tiles, at.row_idx, at.col_id, at.sb_indptr, at.keys2d,
            rtp=at.rtp, vtp=at.vtp,
        )
    )


def mxu_static(at) -> tuple:
    """Hashable geometry for program cache keys: (rows, cols, rtp, vtp,
    ntp)."""
    return (int(at.rows), int(at.cols), int(at.rtp), int(at.vtp),
            int(at.ntp))


def _pad_frontier_words(fwords: jax.Array, rows: int, rtp: int) -> jax.Array:
    """Frontier words padded to the row space + ONE zero pad block (the
    ``row_idx = rtp // TILE`` padding target reads guaranteed zeros)."""
    have = fwords.shape[-1]
    want = rtp // 32 + TILE // 32
    pad = jnp.zeros((*fwords.shape[:-1], want - have), jnp.uint32)
    return jnp.concatenate([fwords, pad], axis=-1)


# bfs_tpu: hot traced
def expand_frontier_mxu_xla(
    fwords: jax.Array, tile_ops: tuple, *, rows: int, cols: int, rtp: int,
    vtp: int,
) -> jax.Array:
    """Bit-identical XLA twin of :func:`expand_frontier_mxu`:
    ``uint32[cols]`` min-original-id candidate per destination
    (``SENT`` where no frontier in-neighbor).  Tiles stream in
    :data:`TWIN_CHUNK`-sized slabs through ``lax.map`` so the unpacked
    (chunk, 4, 128, 32) contribution tensor never scales with the graph;
    uint32 min is exact and order-free, so chunking cannot perturb a
    bit."""
    tiles_wm, row_idx, col_id, _sb, keys_b, _idx = tile_ops
    ntp = row_idx.shape[0]
    nc = ntp // TWIN_CHUNK
    keys2d = keys_b.reshape(-1, TILE)
    fwp = _pad_frontier_words(fwords, rows, rtp)
    fblk = fwp.reshape(-1, TILE_WORDS)[row_idx]  # [ntp, 4]
    shifts = jnp.arange(32, dtype=jnp.uint32)

    def per_chunk(args):
        tk, fb, rk = args  # tk: [chunk, 4, 128] word-major
        lane = jnp.arange(TILE, dtype=jnp.int32)
        fbits = (fb[:, lane >> 5] >> (lane & 31).astype(jnp.uint32)) & 1
        rowmask = jnp.uint32(0) - fbits  # 0 / ~0 per (tile, u)
        contrib = tk & rowmask[:, None, :]  # [chunk, 4, 128]
        bits = (contrib[:, :, :, None] >> shifts) & 1  # [chunk,4,128,32]
        keyrow = keys2d[rk]  # [chunk, 128]
        cand = jnp.min(
            jnp.where(
                bits != 0,
                keyrow[:, None, :, None],
                SENT,
            ),
            axis=2,
        )  # [chunk, 4, 32]
        return cand.reshape(-1, TILE)

    cands = jax.lax.map(
        per_chunk,
        (
            tiles_wm.reshape(nc, TWIN_CHUNK, TILE_WORDS, TILE),
            fblk.reshape(nc, TWIN_CHUNK, TILE_WORDS),
            row_idx.reshape(nc, TWIN_CHUNK),
        ),
    ).reshape(-1, TILE)
    out = jax.ops.segment_min(
        cands, col_id, num_segments=vtp // TILE + 1,
        indices_are_sorted=False,
    )[: vtp // TILE]
    return out.reshape(-1)[:cols]


def _mxu_kernel_factory():
    """One column-superblock per grid step; the per-tile inner loop DMAs
    the frontier block first and early-outs (no tile DMA, no matmul) when
    it is zero.

    Every DMA moves one (8, 128) block, the TPU's native 32-bit tiling:
    tiles travel word-major in pairs (``[ntp/2, 8, 128]``, row ``4*(t&1)+j``
    = word ``j`` of tile ``t`` over its 128 source rows), frontier words
    lane-dense (:data:`_F_BLOCK` tiles per block) and key rows 8 at a
    time; the kernel picks its rows out in registers.  Mosaic has no
    unsigned min, so candidates accumulate as int32 with INT32_MAX for
    SENT (ids are < 2^31) and the caller maps the sentinel back."""
    from jax.experimental import pallas as pl

    # bfs_tpu: hot
    def kernel(sb_ref, idx_hbm, tiles_hbm, fblk_hbm, keys_hbm,
               o_ref, ibuf, tbuf, fbuf, kbuf, sem):
        from jax.experimental.pallas import tpu as pltpu

        pid = pl.program_id(0)
        imax = jnp.int32(np.iinfo(np.int32).max)
        o_ref[...] = jnp.full((SB_TILES, TILE), imax, jnp.int32)
        t0 = sb_ref[pid]
        t1 = sb_ref[pid + 1]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, TILE), 1)

        def body(t, carry):
            @pl.when((t == t0) | (t % _IDX_CHUNK == 0))
            def _():
                cp_i = pltpu.make_async_copy(
                    idx_hbm.at[t // _IDX_CHUNK], ibuf, sem.at[3]
                )
                cp_i.start()
                cp_i.wait()

            cp_f = pltpu.make_async_copy(
                fblk_hbm.at[t // _F_BLOCK], fbuf, sem.at[0]
            )
            cp_f.start()
            cp_f.wait()
            frow = fbuf[pl.ds((t // (_F_BLOCK // _F_ROWS)) % _F_ROWS, 1), :]
            frow = pltpu.bitcast(frow, jnp.int32)
            base = (t % (_F_BLOCK // _F_ROWS)) * TILE_WORDS
            # The tile's 4 frontier words, each as a (1, 1) vector.
            fw4 = [
                jnp.sum(jnp.where(lane == base + j, frow, 0), axis=1,
                        keepdims=True)
                for j in range(TILE_WORDS)
            ]
            nz = jnp.max(
                ((fw4[0] | fw4[1] | fw4[2] | fw4[3]) != 0).astype(jnp.int32)
            ) > 0

            @pl.when(nz)
            def _():
                r = ibuf[0, t % _IDX_CHUNK]
                cl = ibuf[1, t % _IDX_CHUNK]
                cp_t = pltpu.make_async_copy(
                    tiles_hbm.at[t // 2], tbuf, sem.at[1]
                )
                cp_k = pltpu.make_async_copy(
                    keys_hbm.at[r // 8], kbuf, sem.at[2]
                )
                cp_t.start()
                cp_k.start()
                cp_t.wait()
                cp_k.wait()
                odd = (t % 2) == 1
                words = [
                    jnp.where(odd, tbuf[pl.ds(TILE_WORDS + j, 1), :],
                              tbuf[pl.ds(j, 1), :])
                    for j in range(TILE_WORDS)
                ]  # word j of the tile: (1, 128) over source rows
                keys = pltpu.bitcast(kbuf[pl.ds(r % 8, 1), :], jnp.int32)
                # Frontier bit + group weight per source row u, as the
                # [GROUPS, 128] weighted LHS.
                u = jax.lax.broadcasted_iota(jnp.int32, (GROUPS, TILE), 1)
                g = jax.lax.broadcasted_iota(jnp.int32, (GROUPS, TILE), 0)
                fword = jnp.zeros((GROUPS, TILE), jnp.int32)
                for j in range(TILE_WORDS):
                    fword = jnp.where((u >> 5) == j, fw4[j], fword)
                fbit = jax.lax.shift_right_logical(fword, u & 31) & 1
                # Mosaic converts to f32 from i32 only (values < 2^16).
                fw = jnp.where(
                    ((u >> 4) == g) & (fbit == 1), 1 << (u & 15), 0
                ).astype(jnp.float32)
                # The transposed tile: at[v, u] = bit v%32 of word v//32
                # of source row u (destination v on sublanes).
                vv = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0)
                aw = jnp.zeros((TILE, TILE), jnp.uint32)
                for j in range(TILE_WORDS):
                    aw = jnp.where((vv >> 5) == j, words[j], aw)
                at = ((aw >> (vv & 31).astype(jnp.uint32)) & 1).astype(
                    jnp.int32
                ).astype(jnp.float32)
                # THE masked matmul (fw @ at.T): 16-bit contribution masks
                # per group, exact in f32 (sums of distinct powers of two).
                m = jax.lax.dot_general(
                    fw, at, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                masks = m.astype(jnp.int32)  # [GROUPS, 128]
                # Keys of the source rows on sublanes: transpose the
                # broadcast key row.
                keys = jnp.where(keys == -1, imax, keys)
                kcol = jnp.transpose(jnp.broadcast_to(keys, (TILE, TILE)))
                ii = jax.lax.broadcasted_iota(jnp.int32, (16, TILE), 0)
                cand = jnp.full((1, TILE), imax, jnp.int32)
                for gi in range(GROUPS):
                    bits = (masks[gi:gi + 1, :] >> ii) & 1
                    kg = kcol[gi * 16:(gi + 1) * 16, :]
                    cand = jnp.minimum(
                        cand,
                        jnp.min(jnp.where(bits == 1, kg, imax), axis=0,
                                keepdims=True),
                    )
                cur = o_ref[pl.ds(cl, 1), :]
                o_ref[pl.ds(cl, 1), :] = jnp.minimum(cur, cand)

            return carry

        jax.lax.fori_loop(t0, t1, body, jnp.int32(0))

    return kernel


def _pad_rows(x: jax.Array, multiple: int) -> jax.Array:
    pad = (-x.shape[0]) % multiple
    if not pad:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((pad, *x.shape[1:]), x.dtype)], axis=0
    )


# bfs_tpu: hot traced
def expand_frontier_mxu(
    fwords: jax.Array, tile_ops: tuple, *, rows: int, cols: int, rtp: int,
    vtp: int, interpret: bool | None = None,
) -> jax.Array:
    """The fused Pallas expansion: ``uint32[cols]`` min-original-id
    candidates, bit-identical to :func:`expand_frontier_mxu_xla` (the
    PAL005 oracle pins raw bytes).  Grid = one 16384-destination column
    superblock per step (a (128, 128) uint32 output block — the PAL002
    ``mxu=True`` contract); tiles, frontier blocks and key rows stream
    via per-tile DMA with the empty-frontier early-out."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        from .relay_pallas import pallas_interpret

        interpret = pallas_interpret()
    tiles_wm, row_idx, _col_id, sb_indptr, keys_b, idx = tile_ops
    fwp = _pad_frontier_words(fwords, rows, rtp)
    fblk = fwp.reshape(-1, TILE_WORDS)[row_idx]  # [ntp, 4]
    fblk = _pad_rows(fblk, _F_BLOCK).reshape(-1, _F_ROWS, TILE)
    grid = vtp // SB_VERTS
    out = pl.pallas_call(
        _mxu_kernel_factory(),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # sb_indptr
            pl.BlockSpec(memory_space=pl.ANY),  # (row, column) chunks
            pl.BlockSpec(memory_space=pl.ANY),  # tiles (word-major pairs)
            pl.BlockSpec(memory_space=pl.ANY),  # frontier word blocks
            pl.BlockSpec(memory_space=pl.ANY),  # key row blocks
        ],
        out_specs=pl.BlockSpec((SB_TILES, TILE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((vtp // TILE, TILE), jnp.int32),
        scratch_shapes=[
            pltpu.SMEM((2, _IDX_CHUNK), jnp.int32),  # index chunk
            pltpu.VMEM((2 * TILE_WORDS, TILE), jnp.uint32),  # tile pair
            pltpu.VMEM((_F_ROWS, TILE), jnp.uint32),  # frontier block
            pltpu.VMEM((8, TILE), jnp.uint32),  # key rows
            pltpu.SemaphoreType.DMA((4,)),
        ],
        interpret=interpret,
        name="mxu_expand",
    )(sb_indptr, idx, tiles_wm, fblk, keys_b)
    out = out.reshape(-1)[:cols]
    return jnp.where(
        out == np.iinfo(np.int32).max, SENT, out.astype(jnp.uint32)
    )


def _expand(st_fwords, tile_ops, geo: tuple, use_kernel: bool):
    rows, cols, rtp, vtp, _ntp = geo
    if use_kernel:
        return expand_frontier_mxu(
            st_fwords, tile_ops, rows=rows, cols=cols, rtp=rtp, vtp=vtp
        )
    return expand_frontier_mxu_xla(
        st_fwords, tile_ops, rows=rows, cols=cols, rtp=rtp, vtp=vtp
    )


# bfs_tpu: hot traced
def mxu_superstep_packed(st, tile_ops, geo: tuple, use_kernel: bool):
    """One mxu pull superstep on the packed carry: expand -> one
    lexicographic min (the candidate's parent field is the ORIGINAL id —
    the mxu finish decodes it directly, no rank->slot pass)."""
    from . import relay as R

    cand = _expand(st.fwords, tile_ops, geo, use_kernel)
    return R.apply_relay_candidates_packed(st, cand)


# bfs_tpu: hot traced
def mxu_superstep(st, tile_ops, geo: tuple, use_kernel: bool):
    """Unpacked twin (the >62-level fallback carry): parent VALUES are
    original ids (INT32_MAX convention at the apply boundary)."""
    from . import relay as R
    from .relax import INT32_MAX

    cand = _expand(st.fwords, tile_ops, geo, use_kernel)
    cand_i = jnp.where(
        cand == SENT, jnp.int32(INT32_MAX), cand.astype(jnp.int32)
    )
    return R.apply_relay_candidates(st, cand_i)
