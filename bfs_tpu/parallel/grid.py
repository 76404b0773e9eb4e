"""2D grid-sharded BFS: row/column mesh axes over the tile space (ISSUE 17).

The 1D dst-owned mesh (parallel/sharded.py) moves O(V) frontier words per
chip per superstep no matter how many chips participate — the exchange is
one global all-gather, so adding chips shrinks compute but not wire.
This module places the adjacency on an ``r x c`` logical mesh instead
(the classic 2D decomposition of arXiv 1408.1605 / 1208.5542, carried
onto the TPU tile space): cell ``(i, j)`` holds the edges from row
stripe ``R_i`` (source blocks ``[i*c, (i+1)*c)``) into column stripe
``C_j`` (destination blocks ``{i'*c + j}``), and a superstep is

  1. candidate production LOCAL to the cell — dense masked scatter-min
     over the resident edge block, or the budgeted frontier-list gather,
     selected per superstep by the SAME Beamer predicate as every other
     engine (global masses via one scalar ``psum`` over the row axis);
  2. the reduce-axis SIEVE — each cell carries the reached-view of its
     column stripe, so settled destinations never enter the wire;
  3. a ROW-AXIS armed min-reduce of per-destination ORIGINAL-source-id
     candidates (exchange.make_grid_row_reduce) — the mesh column
     settles ``C_j`` (V/c destinations);
  4. the local state update on the owned block, then a COL-AXIS armed
     broadcast of the cell's new frontier words
     (exchange.make_grid_col_exchange) — the mesh row reassembles the
     ``R_i`` frontier (V/r bits) for the next superstep.

Per-chip wire is O(V/r + V/c) = O(V/sqrt(n)) on a square mesh.  At
``1 x n`` the program degenerates to the 1D semantics exactly: the row
reduce is the identity (zero bytes, arm "none") and the column broadcast
IS the 1D exchange — same arms, same budgets, same per-level bytes.

Bit-identity contract (tests/test_grid.py): candidates are min ORIGINAL
source ids — the MXU arm's parent flavor — so dist/parent equal the 1D
mesh and the single-chip engines bit-for-bit at ANY mesh shape; the
direction schedule is bit-identical because the predicate sees the exact
same masses (float32 sums of per-vertex integer out-degrees are exact
below 2^24 edges, so the row-axis ``psum`` re-association cannot drift);
and the column-axis arm schedule and per-level bytes equal the 1D
exchange's, because the column broadcast ships the same sieved frontier
words under the same density vote.

The packed carry is the ``level:6 | origid:26`` word (ops/packed.py, the
mxu flavor), gated on ``packed_parent_fits`` and capped at 62 levels
with the standard truncation re-run, and the segmented twin checkpoints
per-CELL epochs cut at the axis-exchange boundary (resilience/).
"""

from __future__ import annotations

import functools
import os
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, PartitionSpec as P

from .compat import pcast_carry, pcast_varying, shard_map as _shard_map, unvary
from .. import knobs
from ..graph.grid_layout import (
    GRID_KEY_SENTINEL,
    grid_layout_for,
    parse_mesh_spec,
)
from ..models.bfs import BfsResult, check_sources
from ..ops.relax import INT32_MAX

GRID_ROW_AXIS = "row"
GRID_COL_AXIS = "col"


def resolve_grid_mesh(spec: str | None = None) -> tuple[int, int]:
    """``(r, c)`` from an explicit spec or ``BFS_TPU_MESH`` (``"rxc"``);
    no knob -> the 1D degenerate ``1 x num_devices``."""
    if spec is None:
        spec = knobs.get("BFS_TPU_MESH") or ""
    if not spec:
        return 1, len(jax.devices())
    return parse_mesh_spec(spec)


def make_grid_mesh(
    r: int, c: int, *, devices: Sequence[jax.Device] | None = None
) -> Mesh:
    """Build the ``(row, col)`` mesh — row-major over the device list,
    matching the cell index ``i*c + j`` of the layout and the
    checkpoint-shard order."""
    devices = list(devices if devices is not None else jax.devices())
    if r * c > len(devices):
        raise ValueError(
            f"mesh {r}x{c} needs {r * c} devices, have {len(devices)}"
        )
    arr = np.asarray(devices[: r * c]).reshape(r, c)
    return Mesh(arr, (GRID_ROW_AXIS, GRID_COL_AXIS))


def _grid_shape(mesh: Mesh) -> tuple[int, int]:
    return mesh.shape[GRID_ROW_AXIS], mesh.shape[GRID_COL_AXIS]


def _grid_static(layout, packed: bool) -> tuple:
    """Hashable static tuple: (r, c, block, emax, packed)."""
    return (layout.r, layout.c, layout.block, layout.emax, packed)


def _grid_dev_operands(srg, r: int, c: int):
    """Device-resident stacked per-cell layout operands, memoized on the
    layout object (layout data — must not land inside timed repeats)."""
    key = f"_grid_dev_{r}x{c}"
    cached = getattr(srg, key, None)
    if cached is None:
        lo = grid_layout_for(srg, r, c)
        cached = (
            jnp.asarray(lo.esrc), jnp.asarray(lo.edst),
            jnp.asarray(lo.ekey), jnp.asarray(lo.indptr),
        )
        object.__setattr__(srg, key, cached)
    return cached


def _grid_superstep_builder(
    esrc, edst, ekey, indptr, own_all, outdeg, *,
    r: int, c: int, block: int, emax: int, packed: bool,
    cap, telemetry: bool, mode, dir_params, ex_cfg,
):
    """Shared cond/body construction for the fused and segmented grid
    programs (ONE superstep definition — the segment twin must replay the
    fused schedule bit-identically, so they compile the same closure).
    Called INSIDE the shard_map body with per-cell operands."""
    from ..ops.packed import level_word
    from ..ops.relay import pack_std, unpack_std
    from .exchange import make_grid_col_exchange, make_grid_row_reduce

    nw = block // 32
    rb = r * block
    gtot = r * c * block
    kw = own_all.shape[1]
    sent = jnp.uint32(GRID_KEY_SENTINEL)
    i_idx = jax.lax.axis_index(GRID_ROW_AXIS).astype(jnp.int32)
    j_idx = jax.lax.axis_index(GRID_COL_AXIS).astype(jnp.int32)
    cell = i_idx * c + j_idx
    own_local = own_all[cell]
    own_row = jax.lax.dynamic_slice(
        own_all, (i_idx * c, jnp.int32(0)), (c, kw)
    )
    own_cj = jnp.take(own_all.reshape(r, c, kw), j_idx, axis=1)  # [r, kw]
    col_fn = make_grid_col_exchange(
        ex_cfg, kw, nw, r, c, GRID_COL_AXIS, GRID_ROW_AXIS
    )
    row_fn = make_grid_row_reduce(
        ex_cfg, kw, nw, r, c, GRID_ROW_AXIS, GRID_COL_AXIS
    )

    if mode in ("auto", "push"):
        from ..models.bfs import sparse_budgets
        from ..models.direction import frontier_masses_words

        dir_alpha, dir_beta, v_real, e_real = dir_params
        # Global budgets: the SAME derivation as the 1D predicate (so the
        # dispatch agrees superstep-for-superstep); per-cell capacities
        # clamp to the stripe/cell sizes the global predicate bounds.
        bv, _ = sparse_budgets(gtot, gtot)
        _, be_pred = sparse_budgets(gtot, e_real)
        bv_cell, _ = sparse_budgets(c * block, 1)
        _, be_cell = sparse_budgets(gtot, emax)
        outdeg_stripe = jax.lax.dynamic_slice(
            outdeg, (i_idx * c * block,), (c * block,)
        )

        def global_masses(fwr):
            # Per-stripe masses + one scalar psum over the row axis: the
            # R_i stripes partition the vertex space, and float32 sums of
            # integer out-degrees are exact below 2^24 edges, so the
            # re-association vs the 1D single-pass sum cannot drift.
            fs_i, fe_i = frontier_masses_words(
                fwr, outdeg_stripe, c * block
            )
            return (
                jax.lax.psum(fs_i, GRID_ROW_AXIS),
                jax.lax.psum(fe_i, GRID_ROW_AXIS),
            )

        def budget_ok(fsize, fe):
            return (fsize <= bv) & (fe <= jnp.float32(be_pred))

    if telemetry:
        from ..obs import telemetry as T

    def dense_cand(fwr):
        """Dense body: masked scatter-min over ALL resident edges — the
        per-edge frontier bit gates the ORIGINAL-src-id key."""
        w = fwr[esrc >> 5]
        active = ((w >> (esrc & 31).astype(jnp.uint32)) & 1) == 1
        keys = jnp.where(active, ekey, sent)
        return (
            jnp.full((rb,), sent, jnp.uint32)
            .at[edst].min(keys, mode="drop")
        )

    def push_cand(fwr):
        """Push body: budgeted frontier-list gather over the cell CSR
        (the grid twin of _sharded_push_candidates, min-scatter form)."""
        from ..models.bfs import _extract_frontier_list

        flist = _extract_frontier_list(fwr, c * block, bv_cell)
        deg = indptr[flist + 1] - indptr[flist]  # 0 at the c*block fill
        cum = jnp.cumsum(deg)
        starts = indptr[flist]
        j = jnp.arange(be_cell, dtype=jnp.int32)
        owner = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
        owner_c = jnp.clip(owner, 0, bv_cell - 1)
        prev = jnp.where(owner_c > 0, cum[jnp.maximum(owner_c - 1, 0)], 0)
        eidx = starts[owner_c] + (j - prev)
        valid = j < cum[-1]
        eidx = jnp.where(valid, eidx, 0)
        keys = jnp.where(valid, ekey[eidx], sent)
        dst = jnp.where(valid, edst[eidx], jnp.int32(rb))
        return (
            jnp.full((rb,), sent, jnp.uint32)
            .at[dst].min(keys, mode="drop")
        )

    def cond(c_):
        return c_["changed"] & (c_["level"] < cap)

    def body(c_):
        fwr, level, rcv = c_["fw"], c_["level"], c_["rc"]

        # ---- per-superstep body selection (replicated scalar psum) ----
        if mode == "auto":
            from ..models.direction import take_pull

            fsize, fe = global_masses(fwr)
            m_u = jnp.maximum(c_["mu"] - fe, 0.0)
            use_pull = (
                take_pull(
                    c_["prev"], fsize, fe, m_u, v_real, dir_alpha, dir_beta
                )
                | ~budget_ok(fsize, fe)
            )
        elif mode == "push":
            fsize, fe = global_masses(fwr)
            use_pull = ~budget_ok(fsize, fe)
        else:
            use_pull = None

        if use_pull is None:
            cand = dense_cand(fwr)
        else:
            cand = jax.lax.cond(use_pull, dense_cand, push_cand, fwr)

        # ---- reduce-axis SIEVE: settled C_j dsts never enter the wire --
        reached = unpack_std(rcv, rb) != 0
        cand = jnp.where(reached, sent, cand)

        # ---- row-axis armed min-reduce: the column settles C_j ---------
        candg, xbr, xar = row_fn(cand, own_cj)

        # ---- improvement + state update on the owned block -------------
        level2 = level + 1
        imp = pack_std(candg != sent)  # [r*nw] — C_j's new frontier bits
        candg_own = jax.lax.dynamic_slice(candg, (i_idx * block,), (block,))
        fw_own = jax.lax.dynamic_slice(imp, (i_idx * nw,), (nw,))
        out = dict(c_)
        out["rc"] = rcv | imp
        if packed:
            candw = candg_own | level_word(level2)
            out["pk"] = jnp.minimum(c_["pk"], candw)
        else:
            improved = candg_own != sent
            out["dist"] = jnp.where(improved, level2, c_["dist"])
            out["parent"] = jnp.where(
                improved, candg_own.astype(jnp.int32), c_["parent"]
            )

        # ---- col-axis armed broadcast: the row reassembles R_i ---------
        fwr2, xbc, xac = col_fn(fw_own, own_local, own_row)
        cnt = jax.lax.psum(
            jax.lax.population_count(fw_own).sum(dtype=jnp.int32),
            (GRID_ROW_AXIS, GRID_COL_AXIS),
        )
        out["fw"] = fwr2
        out["level"] = level2
        out["changed"] = cnt > 0
        if mode == "auto":
            out["mu"] = m_u
            out["prev"] = use_pull
        if telemetry:
            out["occ"] = T.record_count(c_["occ"], level2, cnt)
            if use_pull is None:
                code = jnp.int32(T.DIR_PULL)
            else:
                code = jnp.where(
                    use_pull, jnp.int32(T.DIR_PULL), jnp.int32(T.DIR_PUSH)
                )
            out["dirs"] = T.record_direction(c_["dirs"], level2, code)
            out["xbc"], out["xac"] = T.record_exchange(
                c_["xbc"], c_["xac"], level2, xbc, xac
            )
            out["xbr"], out["xar"] = T.record_exchange(
                c_["xbr"], c_["xar"], level2, xbr, xar
            )
        return out

    return cond, body, (i_idx, j_idx, cell)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "static", "max_levels", "telemetry", "direction", "exchange",
    ),
)
def _bfs_grid_fused(
    esrc, edst, ekey, indptr, own_words, outdeg, source_new, *,
    mesh, static, max_levels, telemetry: bool = False,
    direction: tuple | None = None, exchange: tuple = ("bitmap", 8),
):
    """The fused 2D grid BFS program: one compiled superstep loop over
    the r x c mesh, two armed collectives per superstep (one per axis),
    per-axis byte/arm telemetry accumulated device-side and pulled once
    at loop exit.  ``static`` is :func:`_grid_static`; ``direction`` the
    ``(mode, alpha, beta, V, E)`` tuple; ``exchange`` the resolved
    ExchangeConfig key."""
    from ..ops.packed import PACKED_SENTINEL, packed_cap
    from .exchange import ExchangeConfig

    r, c, block, emax, packed = static
    nw = block // 32
    gtot = r * c * block
    cap = packed_cap(max_levels) if packed else max_levels
    ex_cfg = ExchangeConfig(*exchange)
    mode = direction[0] if direction is not None else None
    if mode in ("auto", "push"):
        dir_params = (
            float(direction[1]),  # bfs_tpu: ok TRC002 static tuple member
            float(direction[2]),  # bfs_tpu: ok TRC002 static tuple member
            int(direction[3]),  # bfs_tpu: ok TRC002 static tuple member
            int(direction[4]),  # bfs_tpu: ok TRC002 static tuple member
        )
    else:
        dir_params = None

    def inner(esrc_b, edst_b, ekey_b, indptr_b, own_all, outdeg, source):
        cond, body, (i_idx, j_idx, cell) = _grid_superstep_builder(
            esrc_b[0], edst_b[0], ekey_b[0], indptr_b[0], own_all, outdeg,
            r=r, c=c, block=block, emax=emax, packed=packed, cap=cap,
            telemetry=telemetry, mode=mode, dir_params=dir_params,
            ex_cfg=ex_cfg,
        )
        # Initial R_i stripe frontier: the source bit, sliced from the
        # replicated global word space by the row index.
        gw = (
            jnp.zeros((gtot // 32,), jnp.uint32)
            .at[source >> 5]
            .set(jnp.uint32(1) << (source & 31).astype(jnp.uint32))
        )
        fwr = jax.lax.dynamic_slice(gw, (i_idx * c * nw,), (c * nw,))
        # The body's words vary over both axes; they are equal along col.
        fwr = pcast_varying(fwr, (GRID_ROW_AXIS, GRID_COL_AXIS))
        # Initial reached-view of C_j: the source bit iff the source
        # block sits in this mesh column.
        sb = source // block
        within = source - sb * block
        present = (sb % c) == j_idx
        widx = (sb // c) * nw + (within >> 5)
        rc0 = (
            jnp.zeros((r * nw,), jnp.uint32)
            .at[widx]
            .set(
                jnp.where(
                    present,
                    jnp.uint32(1) << (within & 31).astype(jnp.uint32),
                    jnp.uint32(0),
                )
            )
        )
        rc0 = pcast_varying(rc0, (GRID_ROW_AXIS, GRID_COL_AXIS))

        carry = {
            "fw": fwr,
            "rc": rc0,
            "level": jnp.int32(0),
            "changed": jnp.bool_(True),
        }
        lo = cell * block
        ids_local = lo + jnp.arange(block, dtype=jnp.int32)
        if packed:
            carry["pk"] = jnp.where(
                ids_local == source, jnp.uint32(0), PACKED_SENTINEL
            )
        else:
            carry["dist"] = jnp.where(
                ids_local == source, jnp.int32(0), INT32_MAX
            )
            carry["parent"] = jnp.where(
                ids_local == source, source, jnp.int32(-1)
            )
        extras = {}
        if mode == "auto":
            extras["mu"] = outdeg.astype(jnp.float32).sum()
            extras["prev"] = jnp.bool_(False)
        if telemetry:
            from ..obs import telemetry as T

            extras["occ"] = T.init_level_acc()
            extras["dirs"] = T.init_dir_acc()
            extras["xbc"] = T.init_bytes_acc()
            extras["xac"] = T.init_dir_acc()
            extras["xbr"] = T.init_bytes_acc()
            extras["xar"] = T.init_dir_acc()
        carry.update(
            pcast_carry(extras, (GRID_ROW_AXIS, GRID_COL_AXIS))
        )

        out = jax.lax.while_loop(cond, body, carry)
        if packed:
            from ..ops.packed import packed_dist, packed_parent

            dist, parent = packed_dist(out["pk"]), packed_parent(out["pk"])
        else:
            dist, parent = out["dist"], out["parent"]
        if telemetry:
            return (
                dist, parent, out["level"], out["changed"],
                *unvary(
                    (out["occ"], out["dirs"], out["xbc"], out["xac"],
                     out["xbr"], out["xar"]),
                    (GRID_ROW_AXIS, GRID_COL_AXIS),
                ),
            )
        return dist, parent, out["level"], out["changed"]

    both = (GRID_ROW_AXIS, GRID_COL_AXIS)
    fn = _shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            P(both, None), P(both, None), P(both, None), P(both, None),
            P(), P(), P(),
        ),
        out_specs=(
            (P(both), P(both), P(), P(), P(), P(), P(), P(), P(), P())
            if telemetry
            else (P(both), P(both), P(), P())
        ),
        # Fully manual over both mesh axes (same contract as the 1D
        # programs: no partial-auto program exists in this repo).
        axis_names={GRID_ROW_AXIS, GRID_COL_AXIS},
    )
    return fn(esrc, edst, ekey, indptr, own_words, outdeg, source_new)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "static", "max_levels", "telemetry", "direction", "exchange",
    ),
)
def _bfs_grid_segment(
    carry, seg_end, esrc, edst, ekey, indptr, own_words, outdeg, *,
    mesh, static, max_levels, telemetry: bool = False,
    direction: tuple | None = None, exchange: tuple = ("bitmap", 8),
):
    """ONE bounded segment of the grid loop: the checkpointable twin of
    :func:`_bfs_grid_fused` — the identical superstep body (same builder
    closure), stopped at ``seg_end`` supersteps so the host can snapshot
    the carry at the AXIS-EXCHANGE BOUNDARY (after the column broadcast —
    the per-superstep consistency point) and write per-CELL checkpoint
    shards.  A resumed run replays the direction schedule AND both
    per-axis arm sequences bit-identically (the hysteresis pair, the
    reached-views and all six accumulators ride the carry)."""
    from ..ops.packed import packed_cap
    from .exchange import ExchangeConfig

    r, c, block, emax, packed = static
    cap = packed_cap(max_levels) if packed else max_levels
    ex_cfg = ExchangeConfig(*exchange)
    mode = direction[0] if direction is not None else None
    if mode in ("auto", "push"):
        dir_params = (
            float(direction[1]),  # bfs_tpu: ok TRC002 static tuple member
            float(direction[2]),  # bfs_tpu: ok TRC002 static tuple member
            int(direction[3]),  # bfs_tpu: ok TRC002 static tuple member
            int(direction[4]),  # bfs_tpu: ok TRC002 static tuple member
        )
    else:
        dir_params = None
    state_keys = ("pk",) if packed else ("dist", "parent")

    def inner(c_, seg_end, esrc_b, edst_b, ekey_b, indptr_b, own_all,
              outdeg):
        cond0, body, _ = _grid_superstep_builder(
            esrc_b[0], edst_b[0], ekey_b[0], indptr_b[0], own_all, outdeg,
            r=r, c=c, block=block, emax=emax, packed=packed, cap=cap,
            telemetry=telemetry, mode=mode, dir_params=dir_params,
            ex_cfg=ex_cfg,
        )
        c_ = dict(c_)
        c_["fw"] = pcast_varying(c_["fw"], (GRID_ROW_AXIS, GRID_COL_AXIS))
        extras = {
            k: c_[k]
            for k in ("mu", "prev", "occ", "dirs", "xbc", "xac",
                      "xbr", "xar")
            if k in c_
        }
        c_.update(pcast_carry(extras, (GRID_ROW_AXIS, GRID_COL_AXIS)))

        def cond(c_):
            return cond0(c_) & (c_["level"] < seg_end)

        out = jax.lax.while_loop(cond, body, c_)
        # Every leaf but the per-cell state and reached-views is equal
        # along the axes its spec replicates it over.
        return {
            k: v if k in state_keys or k == "rc"
            else unvary(v, (GRID_COL_AXIS,) if k == "fw" else both)
            for k, v in out.items()
        }

    both = (GRID_ROW_AXIS, GRID_COL_AXIS)
    carry_specs = {}
    for k in carry:
        if k in state_keys or k == "rc":
            carry_specs[k] = P(both)
        elif k == "fw":
            carry_specs[k] = P(GRID_ROW_AXIS)
        else:
            carry_specs[k] = P()
    fn = _shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            carry_specs, P(),
            P(both, None), P(both, None), P(both, None), P(both, None),
            P(), P(),
        ),
        out_specs=carry_specs,
        axis_names={GRID_ROW_AXIS, GRID_COL_AXIS},
    )
    return fn(carry, seg_end, esrc, edst, ekey, indptr, own_words, outdeg)


def grid_segment_keys(packed: bool, auto: bool, telemetry: bool) -> list[str]:
    """The grid segment carry's key set — the ONE definition
    :func:`grid_segment_carry` builds from and the restore gate validates
    against.  ``rc`` (the per-cell reached-views) is exact loop state: a
    resume without it would re-admit settled destinations into the
    row-axis wire and change the per-axis byte curves."""
    keys = (["pk"] if packed else ["dist", "parent"]) + [
        "fw", "rc", "level", "changed",
    ]
    if auto:
        keys += ["mu", "prev"]
    if telemetry:
        keys += ["occ", "dirs", "xbc", "xac", "xbr", "xar"]
    return keys


def grid_segment_carry(srg, r: int, c: int, source_new: int, packed: bool,
                       auto: bool, telemetry: bool, outdeg_dev,
                       restore: dict | None = None) -> dict:
    """Initial (or checkpoint-restored) global-view carry for
    :func:`_bfs_grid_segment`.  Global layouts: state ``[gtot]``
    (cell-major — cell ``i*c+j`` owns block ``i*c+j``), ``fw``
    ``[gtot/32]`` (the full frontier word space, row-stripe partitioned),
    ``rc`` ``[n * r*nw]`` (cell-major stack of per-cell C_j
    reached-views)."""
    from ..obs import telemetry as T
    from ..ops.packed import PACKED_SENTINEL

    n = r * c
    block = srg.block
    gtot = n * block
    nw = block // 32
    keys = grid_segment_keys(packed, auto, telemetry)
    if restore is not None:
        return {k: jnp.asarray(restore[k]) for k in keys}
    if packed:
        pk = np.full(gtot, PACKED_SENTINEL, np.uint32)
        pk[source_new] = np.uint32(0)
        carry = {"pk": jnp.asarray(pk)}
    else:
        dist = np.full(gtot, INT32_MAX, np.int32)
        dist[source_new] = 0
        parent = np.full(gtot, -1, np.int32)
        parent[source_new] = source_new
        carry = {"dist": jnp.asarray(dist), "parent": jnp.asarray(parent)}
    fw = np.zeros(gtot // 32, np.uint32)
    fw[source_new >> 5] = np.uint32(1) << np.uint32(source_new & 31)
    rc = np.zeros((n, r * nw), np.uint32)
    sb = source_new // block
    widx = (sb // c) * nw + ((source_new % block) >> 5)
    bit = np.uint32(1) << np.uint32(source_new & 31)
    for i in range(r):
        rc[i * c + sb % c, widx] = bit
    carry.update(
        fw=jnp.asarray(fw), rc=jnp.asarray(rc.reshape(-1)),
        level=jnp.int32(0), changed=jnp.bool_(True),
    )
    if auto:
        carry["mu"] = outdeg_dev.astype(jnp.float32).sum()
        carry["prev"] = jnp.bool_(False)
    if telemetry:
        carry["occ"] = T.init_level_acc()
        carry["dirs"] = T.init_dir_acc()
        carry["xbc"] = T.init_bytes_acc()
        carry["xac"] = T.init_dir_acc()
        carry["xbr"] = T.init_bytes_acc()
        carry["xar"] = T.init_dir_acc()
    return carry


def _prepare_grid(graph, n: int):
    from ..graph.relay import ShardedRelayGraph, build_sharded_relay_graph

    if isinstance(graph, ShardedRelayGraph):
        if graph.num_shards != n:
            raise ValueError(
                f"ShardedRelayGraph has {graph.num_shards} shards but the "
                f"grid has {n} cells; rebuild with num_shards={n}"
            )
        return graph
    return build_sharded_relay_graph(graph, n)


def _grid_curve(accs, *, dir_cfg, ex_cfg, kw, nw, r, c, cap, num_levels):
    from ..obs.telemetry import (
        direction_schedule,
        level_curve,
        read_telemetry,
    )
    from .exchange import grid_exchange_report

    fv, dirs, xbc, xac, xbr, xar = read_telemetry(accs)
    curve = level_curve(fv, cap=cap)
    curve["direction_schedule"] = direction_schedule(
        dirs, mode=dir_cfg.mode, alpha=dir_cfg.alpha, beta=dir_cfg.beta
    )
    curve["exchange"] = grid_exchange_report(
        xbc, xac, xbr, xar, ex_cfg, kw, nw, r, c, num_levels=num_levels
    )
    return curve


def bfs_grid(
    graph,
    source: int = 0,
    *,
    mesh: Mesh | None = None,
    max_levels: int | None = None,
    telemetry: bool = False,
    direction: str | None = None,
    exchange: str | None = None,
):
    """2D grid-sharded BFS — the host entry point (``BFS_TPU_MESH=rxc``
    selects the mesh shape when ``mesh`` is not given).  Accepts a
    :class:`~bfs_tpu.graph.csr.Graph` or a prebuilt ``r*c``-shard
    ShardedRelayGraph; returns :class:`~bfs_tpu.models.bfs.BfsResult`
    (plus the level curve with per-axis ``details.exchange`` under
    ``telemetry=True``) — dist/parent bit-identical to the 1D mesh and
    the single-chip engines."""
    from ..models.direction import resolve_direction
    from ..ops.packed import (
        PACKED_MAX_LEVELS,
        packed_parent_fits,
        packed_truncated,
        resolve_packed,
    )
    from .exchange import resolve_exchange
    from .sharded import _own_word_table_dev, _relay_map_back

    if mesh is None:
        r, c = resolve_grid_mesh()
        mesh = make_grid_mesh(r, c)
    r, c = _grid_shape(mesh)
    n = r * c
    dir_cfg = resolve_direction(direction)
    ex_cfg = resolve_exchange(exchange)
    srg = _prepare_grid(graph, n)
    check_sources(srg.num_vertices, source)
    max_levels = (
        int(max_levels) if max_levels is not None else srg.num_vertices
    )
    source_new = int(srg.old2new[source])
    layout = grid_layout_for(srg, r, c)
    operands = _grid_dev_operands(srg, r, c)
    own_dev = _own_word_table_dev(srg)
    outdeg_dev = jnp.asarray(srg.outdeg)
    direction_static = (
        dir_cfg.mode, dir_cfg.alpha, dir_cfg.beta,
        srg.num_vertices, srg.num_edges,
    )
    src_dev = jnp.int32(source_new)

    def run_flavor(packed: bool):
        out = _bfs_grid_fused(
            *operands, own_dev, outdeg_dev, src_dev,
            mesh=mesh, static=_grid_static(layout, packed),
            max_levels=max_levels, telemetry=telemetry,
            direction=direction_static, exchange=ex_cfg.key(),
        )
        dist, parent, level, changed = out[:4]
        return (
            np.asarray(jax.device_get(dist)),
            np.asarray(jax.device_get(parent)),
            int(jax.device_get(level)), bool(jax.device_get(changed)),
            out[4:],
        )

    packed = resolve_packed(packed_parent_fits(srg.num_vertices))
    dist, parent, level, changed, accs = run_flavor(packed)
    if packed and packed_truncated(changed, level, max_levels):
        # Cap exit with room left: the search is deeper than the 62-level
        # packed field — re-run unpacked (same contract as every packed
        # engine; the host wrapper owns the fallback).
        packed = False
        dist, parent, level, changed, accs = run_flavor(packed)
    dist, parent = _relay_map_back(srg, dist, parent, source, "mxu")
    result = BfsResult(dist=dist, parent=parent, num_levels=level)
    if not telemetry:
        return result
    cap = min(PACKED_MAX_LEVELS, max_levels) if packed else max_levels
    curve = _grid_curve(
        accs, dir_cfg=dir_cfg, ex_cfg=ex_cfg, kw=int(own_dev.shape[1]),
        nw=srg.block // 32, r=r, c=c, cap=cap,
        num_levels=result.num_levels,
    )
    return result, curve


def bfs_grid_segmented(
    graph,
    source: int = 0,
    *,
    mesh: Mesh | None = None,
    ckpt,
    max_levels: int | None = None,
    telemetry: bool = False,
    direction: str | None = None,
    exchange: str | None = None,
):
    """Segmented-with-checkpoints grid BFS: the resumable twin of
    :func:`bfs_grid` — bit-identical dist/parent, direction schedule and
    BOTH per-axis exchange-arm sequences for any segmentation.  Each
    segment ends at the axis-exchange boundary; one epoch = per-CELL
    state shards (``ckpt.shards == r*c``, cell-major — the same shard
    files a 1D run at ``n`` shards would cut, so shard-loss fallback is
    shared machinery) plus a meta file carrying the frontier words, the
    reached-views, the hysteresis pair and all six accumulators."""
    import time as _time

    from ..models.direction import resolve_direction
    from ..ops.packed import (
        PACKED_MAX_LEVELS,
        packed_cap,
        packed_parent_fits,
        packed_truncated,
        resolve_packed,
    )
    from ..resilience.superstep_ckpt import restore_arrays
    from .exchange import resolve_exchange
    from .sharded import _own_word_table_dev, _relay_map_back

    if mesh is None:
        r, c = resolve_grid_mesh()
        mesh = make_grid_mesh(r, c)
    r, c = _grid_shape(mesh)
    n = r * c
    dir_cfg = resolve_direction(direction)
    ex_cfg = resolve_exchange(exchange)
    srg = _prepare_grid(graph, n)
    if getattr(ckpt, "shards", 1) != n:
        raise ValueError(
            f"checkpointer built for {getattr(ckpt, 'shards', 1)} shards "
            f"but the {r}x{c} grid has {n} cells"
        )
    check_sources(srg.num_vertices, source)
    max_levels = (
        int(max_levels) if max_levels is not None else srg.num_vertices
    )
    source_new = int(srg.old2new[source])
    block = srg.block
    layout = grid_layout_for(srg, r, c)
    operands = _grid_dev_operands(srg, r, c)
    own_dev = _own_word_table_dev(srg)
    outdeg_dev = jnp.asarray(srg.outdeg)
    auto = dir_cfg.mode == "auto"
    direction_static = (
        dir_cfg.mode, dir_cfg.alpha, dir_cfg.beta,
        srg.num_vertices, srg.num_edges,
    )

    def run_flavor(packed: bool):
        cap = packed_cap(max_levels) if packed else max_levels
        state_keys = ("pk",) if packed else ("dist", "parent")
        meta_arrays, shard_arrays = restore_arrays(
            ckpt, packed,
            require=tuple(
                k for k in grid_segment_keys(packed, auto, telemetry)
                if k not in state_keys
            ),
            require_shards=state_keys,
        )
        restore = None
        if meta_arrays is not None:
            restore = dict(meta_arrays)
            for k in state_keys:
                restore[k] = np.concatenate([sa[k] for sa in shard_arrays])
        carry = grid_segment_carry(
            srg, r, c, source_new, packed, auto, telemetry, outdeg_dev,
            restore=restore,
        )
        level, changed = jax.device_get((carry["level"], carry["changed"]))
        while bool(changed) and int(level) < cap:
            seg_end = jax.device_put(
                np.int32(min(int(level) + ckpt.interval(), cap))
            )
            t0 = _time.perf_counter()
            carry = _bfs_grid_segment(
                carry, seg_end, *operands, own_dev, outdeg_dev,
                mesh=mesh, static=_grid_static(layout, packed),
                max_levels=max_levels, telemetry=telemetry,
                direction=direction_static, exchange=ex_cfg.key(),
            )
            new_level, changed = jax.device_get(
                (carry["level"], carry["changed"])
            )
            seg_s = _time.perf_counter() - t0
            meta_arrays, shard_arrays = {}, []
            if ckpt.enabled:
                host = {
                    k: np.asarray(v)
                    for k, v in jax.device_get(carry).items()
                }
                meta_arrays = {
                    k: v for k, v in host.items() if k not in state_keys
                }
                meta_arrays["packed_flag"] = np.int32(packed)
                shard_arrays = [
                    {k: host[k][s * block:(s + 1) * block]
                     for k in state_keys}
                    for s in range(n)
                ]
            ckpt.save_epoch(int(new_level), meta_arrays, shard_arrays)
            ckpt.note_segment(int(new_level) - int(level), seg_s)
            level = new_level
        if packed:
            from ..ops.packed import unpack_host

            dist, parent = unpack_host(
                np.asarray(jax.device_get(carry["pk"]))
            )
        else:
            dist = np.asarray(jax.device_get(carry["dist"]))
            parent = np.asarray(jax.device_get(carry["parent"]))
        return carry, dist, parent, int(level), bool(changed)

    packed = resolve_packed(packed_parent_fits(srg.num_vertices))
    carry, dist, parent, level, changed = run_flavor(packed)
    if packed and packed_truncated(changed, level, max_levels):
        ckpt.clear()
        packed = False
        carry, dist, parent, level, changed = run_flavor(packed)
    dist, parent = _relay_map_back(srg, dist, parent, source, "mxu")
    result = BfsResult(dist=dist, parent=parent, num_levels=level)
    ckpt.clear()
    if not telemetry:
        return result
    cap = min(PACKED_MAX_LEVELS, max_levels) if packed else max_levels
    curve = _grid_curve(
        (carry["occ"], carry["dirs"], carry["xbc"], carry["xac"],
         carry["xbr"], carry["xar"]),
        dir_cfg=dir_cfg, ex_cfg=ex_cfg, kw=int(own_dev.shape[1]),
        nw=block // 32, r=r, c=c, cap=cap, num_levels=result.num_levels,
    )
    return result, curve
