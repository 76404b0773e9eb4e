"""Mesh-sharded BFS: `shard_map` over edge shards with ICI all-reduce merge.

TPU-native re-design of the reference's only parallelism strategy — Spark
data-parallel map/shuffle over hash-partitioned Vertex records
(BfsSpark.java:66-108, SURVEY.md §2.4/§2.5):

  * Spark's hash-partitioned RDD blocks  ->  balanced dst-sorted edge shards,
    one per device along the mesh's ``graph`` axis (csr.build_device_graph).
  * The shuffle (`reduceByKey`) + driver collect (`collectAsMap`)  ->  one
    ``lax.pmin`` all-reduce of the per-destination candidate-parent array per
    superstep, riding ICI.  No host round-trip: the whole superstep loop is
    a single compiled program, and dist/parent/frontier stay replicated
    device-resident.
  * The driver's file-based termination scan (BfsSpark.java:117)  ->  an
    on-device replicated scalar.

A second mesh axis ``batch`` shards the sources axis of batched multi-source
BFS (data parallelism); ``graph`` is the model/context-parallel analogue.
This is the scaling design for graphs that exceed one chip's HBM: per-device
edge memory is E/n while V-sized state is replicated (SURVEY.md §5
long-context row: graph sharding is this workload's context parallelism).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, PartitionSpec as P

from .compat import pcast_carry, pcast_varying, shard_map as _shard_map, unvary
from .. import knobs

from ..graph.csr import DeviceGraph, Graph, build_device_graph
from ..graph.ell import ShardedPullGraph, build_sharded_pull_graph
from ..models.bfs import BfsResult, check_sources
from ..models.multisource import MultiBfsResult
from ..ops.pull import (
    pack_frontier_block,
    pull_candidates_rows,
    unpack_frontier_blocks,
)
from ..ops.relax import (
    INT32_MAX,
    BfsState,
    init_batched_state,
    init_state,
    relax_superstep,
    relax_superstep_batched,
)

GRAPH_AXIS = "graph"
BATCH_AXIS = "batch"


def make_mesh(
    graph: int | None = None,
    batch: int = 1,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a ``(batch, graph)`` mesh.  ``graph=None`` uses all remaining
    devices.  Single-host multi-device or multi-host both work — the mesh is
    the cluster-bootstrap analogue of the Spark master/worker setup
    (service.properties ip/port + README.md:27-31), minus the processes."""
    devices = list(devices if devices is not None else jax.devices())
    if graph is None:
        graph = len(devices) // batch
    if batch * graph > len(devices):
        raise ValueError(f"mesh {batch}x{graph} needs {batch * graph} devices, have {len(devices)}")
    arr = np.asarray(devices[: batch * graph]).reshape(batch, graph)
    return Mesh(arr, (BATCH_AXIS, GRAPH_AXIS))


def _graph_shards(mesh: Mesh) -> int:
    return mesh.shape[GRAPH_AXIS]


def _reject_wrong_layout_for_push(graph) -> None:
    from ..graph.relay import ShardedRelayGraph

    if isinstance(graph, ShardedPullGraph):
        raise ValueError("a ShardedPullGraph only runs on engine='pull'")
    if isinstance(graph, ShardedRelayGraph):
        raise ValueError("a ShardedRelayGraph only runs on engine='relay'")


def _prepare(graph: Graph | DeviceGraph, mesh: Mesh, block: int) -> DeviceGraph:
    n = _graph_shards(mesh)
    if isinstance(graph, DeviceGraph):
        if graph.num_shards != n:
            raise ValueError(
                f"DeviceGraph has {graph.num_shards} shards but mesh axis "
                f"'{GRAPH_AXIS}' has {n}; rebuild with build_device_graph(num_shards={n})"
            )
        return graph
    return build_device_graph(graph, num_shards=n, block=block)


@functools.partial(
    jax.jit, static_argnames=("mesh", "num_vertices", "max_levels")
)
def _bfs_sharded_fused(src, dst, source, *, mesh, num_vertices, max_levels):
    def inner(src_blk, dst_blk, source):
        src_e = src_blk.reshape(-1)
        dst_e = dst_blk.reshape(-1)
        state = init_state(num_vertices, source)

        def cond(s: BfsState):
            return s.changed & (s.level < max_levels)

        def body(s: BfsState):
            return relax_superstep(s, src_e, dst_e, axis_name=GRAPH_AXIS)

        return jax.lax.while_loop(cond, body, state)

    fn = _shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(GRAPH_AXIS, None), P(GRAPH_AXIS, None), P()),
        out_specs=BfsState(P(), P(), P(), P(), P()),
        axis_names={GRAPH_AXIS},
    )
    return fn(src, dst, source)


def _init_block_state(source, block: int):
    """Per-device dist/parent init over the owned vertex block (ids are
    GLOBAL: ``axis_index*block + local``); the source's parent self-entry is
    in whatever id space ``source`` lives in — host wrappers fix it up."""
    lo = jax.lax.axis_index(GRAPH_AXIS).astype(jnp.int32) * block
    ids_local = lo + jnp.arange(block, dtype=jnp.int32)
    is_src = ids_local == source
    dist = jnp.where(is_src, jnp.int32(0), INT32_MAX)
    parent = jnp.where(is_src, source, jnp.int32(-1))
    return dist, parent


def _packed_source_frontier(source, block: int, n: int):
    """Initial global standard-packed frontier words with only the source
    bit set.  Every device computes it identically (no collective), then
    `pcast` aligns the carry with the all_gather-refreshed words of the loop
    body, which are graph-axis-varying."""
    fwords = (
        jnp.zeros((n * block // 32,), jnp.uint32)
        .at[source >> 5]
        .set(jnp.uint32(1) << (source & 31).astype(jnp.uint32))
    )
    return pcast_varying(fwords, (GRAPH_AXIS,))


def _apply_block_candidates(carry, cand, nw: int):
    """Shared superstep tail for block-partitioned engines: mark newly
    reached owned vertices, advance the level, exchange the new frontier as
    a bit-packed all-gather, and all-reduce the termination flag."""
    dist, parent, _, level, _ = carry
    improved = (cand != INT32_MAX) & (dist == INT32_MAX)
    level = level + 1
    dist = jnp.where(improved, level, dist)
    parent = jnp.where(improved, cand, parent)
    fwords = jax.lax.all_gather(
        pack_frontier_block(improved, nw), GRAPH_AXIS, tiled=True
    )
    changed = jax.lax.pmax(improved.any().astype(jnp.int32), GRAPH_AXIS) > 0
    return dist, parent, fwords, level, changed


@functools.partial(jax.jit, static_argnames=("mesh", "block", "max_levels"))
def _bfs_sharded_pull_fused(ell0, folds, source, *, mesh, block, max_levels):
    """Vertex-partitioned pull BFS: per-device ELL over owned destinations,
    replicated frontier refreshed by a bit-packed all-gather (1 bit/vertex
    over ICI per superstep — vs the full int32[V+1] `pmin` of the push
    formulation, a 256x smaller exchange), dist/parent fully distributed."""
    n = mesh.shape[GRAPH_AXIS]
    vtot = n * block
    nw = block // 32

    def inner(ell0_blk, folds_blk, source):
        ell0_blk = ell0_blk[0]
        folds_blk = tuple(f[0] for f in folds_blk)
        dist, parent = _init_block_state(source, block)
        fwords = _packed_source_frontier(source, block, n)
        gids = jnp.arange(vtot, dtype=jnp.int32)
        inf1 = jnp.full((1,), INT32_MAX, dtype=jnp.int32)

        def cond(carry):
            _, _, _, level, changed = carry
            return changed & (level < max_levels)

        def body(carry):
            bits = unpack_frontier_blocks(carry[2], n, nw)
            ftab_ext = jnp.concatenate([jnp.where(bits, gids, INT32_MAX), inf1])
            cand = pull_candidates_rows(ftab_ext, ell0_blk, folds_blk, block)
            return _apply_block_candidates(carry, cand, nw)

        dist, parent, _, level, _ = jax.lax.while_loop(
            cond, body, (dist, parent, fwords, jnp.int32(0), jnp.bool_(True))
        )
        return dist, parent, level

    fn = _shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            P(GRAPH_AXIS, None, None),
            tuple(P(GRAPH_AXIS, None, None) for _ in folds),
            P(),
        ),
        out_specs=(P(GRAPH_AXIS), P(GRAPH_AXIS), P()),
        axis_names={GRAPH_AXIS},
    )
    return fn(ell0, folds, source)


def _relay_candidates_shard(
    fwords_global, vperm_blk, net_blk, valid_blk, *, static
):
    """One shard's gather-free candidate pipeline (v4): global standard-
    packed frontier words -> this shard's per-owned-vertex min active L1
    slot (unpacked path) or min active within-row RANK as
    ``uint32 | PACKED_SENTINEL`` (packed path — ops/relay.rowmin_ranks,
    the masked row-min over valid slots only).  With v4's standard packing
    the all-gathered words ARE the global frontier in vperm element order
    (relabeling is shard-major), so they feed the butterflies directly
    with no repacking.

    With ``use_pallas`` in ``static`` the networks run as the SAME fused
    3-pass Pallas kernels as the single-chip engine (ops/relay_pallas.py) —
    inside ``shard_map`` a Pallas call is a per-device kernel, so the mesh
    path no longer pays the per-stage launch train (~55 x ~0.4 ms/superstep
    on real chips — VERDICT r3 weak #5); mask operands are then the
    per-pass prepared arrays (tuples), not the flat stream."""
    from ..ops import relay as R

    (block, vperm_size, vperm_table, out_classes, out_space, net_table,
     net_size, in_classes, n, use_pallas, packed, _expansion) = static
    if use_pallas:
        from ..ops import relay_pallas as RP
    nw = block // 32
    zpad = jnp.zeros(vperm_size // 32 - n * nw, jnp.uint32)
    fw = jnp.concatenate([fwords_global, zpad])
    if use_pallas and isinstance(vperm_blk, tuple):
        y = RP.apply_benes_fused(
            fw, vperm_blk, RP.pass_static(vperm_table, vperm_size),
            vperm_size, vma={GRAPH_AXIS},
        )
    else:
        y = R.apply_benes_std(fw, vperm_blk, vperm_table, vperm_size)
    l2 = R.broadcast_l2(y, out_classes, net_size, out_space)
    if use_pallas and isinstance(net_blk, tuple):
        l1 = RP.apply_benes_fused(
            l2, net_blk, RP.pass_static(net_table, net_size),
            net_size, vma={GRAPH_AXIS},
        )
    else:
        l1 = R.apply_benes_std(l2, net_blk, net_table, net_size)
    if packed:
        return R.rowmin_ranks(l1, valid_blk, in_classes, block)
    return R.rowmin_candidates(l1, valid_blk, in_classes, block)


def _sharded_relay_static(srg, n: int, use_pallas: bool = False,
                          packed: bool = False,
                          expansion: tuple = ("gather",)):
    """The sharded program's hashable static tuple.  ``expansion`` is the
    arm element (ISSUE 15): ``('gather',)`` or ``('mxu', geometry,
    use_kernel)`` — appended last, read back via :func:`_static_parts`."""
    return (
        srg.block, srg.vperm_size, srg.vperm_table, tuple(srg.out_classes),
        srg.out_space, srg.net_table, srg.net_size, tuple(srg.in_classes), n,
        use_pallas, packed, expansion,
    )


def _static_parts(static) -> tuple:
    """(block, in_classes, packed, expansion) from the static tuple."""
    return static[0], static[7], static[10], static[11]


def _mxu_candidates_shard(fw_global, tile_blk, *, expansion, packed):
    """One shard's MXU candidate pipeline (ISSUE 15): the all-gathered
    global frontier words against this shard's (global src x local dst)
    adjacency tiles — min ORIGINAL source id per owned destination, in
    the shared candidate format (``uint32 | PACKED_SENTINEL`` packed,
    ``int32 | INT32_MAX`` unpacked) so the body-agnostic superstep tail
    (sieve, exchange, state update) is untouched."""
    from ..ops import relay_mxu as RM

    _, geo, use_kernel = expansion
    rows, cols, rtp, vtp, _ntp = geo
    expand = RM.expand_frontier_mxu if use_kernel else RM.expand_frontier_mxu_xla
    cand = expand(fw_global, tuple(tile_blk), rows=rows, cols=cols, rtp=rtp,
                  vtp=vtp)
    if packed:
        return cand
    return jnp.where(
        cand == jnp.uint32(0xFFFFFFFF),
        jnp.int32(INT32_MAX), cand.astype(jnp.int32),
    )


def _resolve_sharded_applier(applier: str) -> bool:
    """'auto' -> fused Pallas on TPU backends (sizes permitting), XLA
    elsewhere; 'pallas'/'xla' force.  No per-init probe here — the sharded
    program is AOT-compiled once per mesh and the single-chip probe's
    selection applies to the same kernels."""
    from ..ops.relay_pallas import pallas_enabled

    if applier == "pallas":
        return True
    if applier == "xla":
        return False
    if applier != "auto":
        raise ValueError(
            f"unknown applier {applier!r}; use 'auto', 'pallas' or 'xla'"
        )
    return pallas_enabled()


def _sharded_relay_mask_args(srg, use_pallas: bool):
    """Device mask operands, stacked over the shard axis.  Pallas form: per
    network a TUPLE of per-pass arrays, each [n_shards, rows, 128] with the
    per-shard rearranged copies (ops/relay_pallas.prepare_pass_masks)."""
    if not use_pallas:
        return jnp.asarray(srg.vperm_masks), jnp.asarray(srg.net_masks)
    from ..ops import relay_pallas as RP

    def prep(masks_all, table, size):
        if not RP.pallas_net_ok(size):
            return jnp.asarray(masks_all)
        per = [
            RP.prepare_pass_masks(np.asarray(masks_all[s]), table, size)
            for s in range(srg.num_shards)
        ]
        return tuple(
            jnp.asarray(np.stack([p[i] for p in per]))
            for i in range(len(per[0]))
        )

    return (
        prep(srg.vperm_masks, srg.vperm_table, srg.vperm_size),
        prep(srg.net_masks, srg.net_table, srg.net_size),
    )


def _strip_shard_dim(x):
    """Remove the leading shard axis from a mask operand (array or tuple of
    per-pass arrays) inside ``shard_map``."""
    return tuple(a[0] for a in x) if isinstance(x, tuple) else x[0]


def _mask_specs(x):
    """Matching in_specs pytree for a mask operand."""
    return (
        tuple(P(GRAPH_AXIS) for _ in x)
        if isinstance(x, tuple)
        else P(GRAPH_AXIS, None)
    )


#: AOT-compiled sharded relay programs (the scoped-vmem compiler options the
#: fused kernels need cannot go through XLA_FLAGS — models/bfs.py).
#: Bounded: oldest executable evicted past 8 entries (keys are
#: graph-specific, so a long-lived process over many graphs/scales would
#: otherwise retain every compiled program forever).
_SHARDED_AOT_CACHE: dict = {}
_SHARDED_AOT_CACHE_MAX = 8


def _sharded_push_candidates(
    fw, adj_indptr, adj_dst, adj_slot, unreached, *,
    gtot: int, block: int, bv: int, be: int, packed: bool,
):
    """Push (sparse gather) candidate producer for one shard: extract the
    GLOBAL frontier list from the all-gathered words, fan out to this
    shard's dst-owned adjacency slice, min-merge per owned destination by
    a (local dst, slot) sort, and emit candidates in the SAME per-owned-
    vertex format as the dense relay pipeline (min L1 slot unpacked, min
    within-row rank ``| PACKED_SENTINEL`` packed) — so the shared
    superstep tail (sieve, exchange, state update) is body-agnostic and
    the two bodies are bit-exact for any schedule.

    ``unreached``: bool[block] — the SIEVE applied at the producer: a
    settled destination never yields a candidate, so its bit can never
    re-enter the exchange.  The shapes are the clamped sparse budgets
    (``bv`` global frontier vertices, ``be`` edges into this shard);
    dispatch guarantees they hold (models/bfs.sparse_budgets — the same
    derivation the predicate uses, so capacity and dispatch can never
    disagree)."""
    from ..models.bfs import _extract_frontier_list
    from ..ops.packed import PACKED_SENTINEL

    flist = _extract_frontier_list(fw, gtot, bv)
    deg = adj_indptr[flist + 1] - adj_indptr[flist]  # 0 at the gtot fill
    cum = jnp.cumsum(deg)
    starts = adj_indptr[flist]
    j = jnp.arange(be, dtype=jnp.int32)
    owner = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
    owner_c = jnp.clip(owner, 0, bv - 1)
    prev = jnp.where(owner_c > 0, cum[jnp.maximum(owner_c - 1, 0)], 0)
    eidx = starts[owner_c] + (j - prev)
    valid = j < cum[-1]
    eidx = jnp.where(valid, eidx, 0)
    dstv = adj_dst[eidx]  # LOCAL owned ids [0, block)
    slot = adj_slot[eidx]  # L1 slots (unpacked) / within-row ranks (packed)
    dk, sk = jax.lax.sort(
        (jnp.where(valid, dstv, jnp.int32(block)), slot), num_keys=2
    )
    first = (
        jnp.concatenate([jnp.ones(1, bool), dk[1:] != dk[:-1]])
        & (dk < block)
    )
    upd = first & unreached[jnp.clip(dk, 0, block - 1)]
    tgt = jnp.where(upd, dk, jnp.int32(block))  # block = dropped
    if packed:
        return (
            jnp.full(block, PACKED_SENTINEL, jnp.uint32)
            .at[tgt].set(sk.astype(jnp.uint32), mode="drop")
        )
    return (
        jnp.full(block, INT32_MAX, jnp.int32)
        .at[tgt].set(sk, mode="drop")
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "static", "max_levels", "telemetry", "direction",
        "exchange", "sparse",
    ),
)
def _bfs_sharded_relay_fused(
    vperm_masks, net_masks, valid_words, own_words,
    adj_indptr, adj_dst, adj_slot, outdeg, source_new, *,
    mesh, static, max_levels, telemetry: bool = False,
    direction: tuple | None = None, exchange: tuple = ("bitmap", 8),
    sparse: bool = False,
):
    """Vertex-partitioned relay BFS (v4): per-shard Beneš layouts (one
    unified SPMD program, per-device mask data), frontier exchanged
    through the compressed-exchange arms of :mod:`..parallel.exchange`.
    State lives in the GLOBAL RELABELED space — dist/parent fully
    distributed, parent VALUES are per-shard L1 slot indices (converted
    to original src ids on the host, bfs_sharded).

    ``exchange`` is the resolved :class:`~.exchange.ExchangeConfig` key:
    ``flat`` all-gathers the whole owned word range (the oracle),
    ``bitmap`` the sieved real-word table, ``auto``/``delta`` the
    word-list arm with its on-device density fallback.  The superstep is
    structured for OVERLAP: the exchange collective on the new frontier
    words is issued as soon as the improvement mask exists, BEFORE the
    O(V/n) state writes — the gathered words land in a fresh buffer (the
    previous frontier stays live as the candidate pipeline's operand, a
    double-buffered carry), so XLA's scheduler can fly the all-gather
    over the local state update and the termination ``pmax`` rides the
    same window.

    ``direction`` — ``(mode, alpha, beta, V_real, E_real)`` — selects the
    superstep body per level once ``sparse`` ships the per-shard
    dst-owned adjacency: ``pull`` runs the dense relay pipeline every
    superstep, ``push`` the sparse gather wherever the static budgets
    allow (the legacy hybrid dispatch), ``auto`` the Beamer predicate
    (models/direction.py take_pull — the SAME single definition the
    single-chip programs compile, fed the real V/E so the schedule is
    bit-identical to the single-chip relay engine's for the same graph
    and thresholds).  Both bodies emit candidates in one format; the
    decision is a pure function of replicated on-device state (the
    global frontier words), so no collective and no host sync is needed
    to agree on the branch.

    With ``packed`` in ``static`` each shard carries ONE uint32
    ``level:6|rank:26`` word per owned vertex (half the per-superstep
    state HBM bytes), the update is one lexicographic min, and the
    dist/parent-slot outputs are unpacked once at loop exit — the
    exchange ships frontier bits either way.  The loop caps at
    PACKED_MAX_LEVELS; ``changed`` is returned so the host wrapper can
    detect a cap exit and re-run unpacked.

    With ``telemetry`` (static) the carry additionally holds the
    per-level occupancy, direction-schedule, exchange-bytes and
    exchange-arm accumulators (obs/telemetry.py), fed the GLOBAL
    all-gathered frontier words — identical on every shard, so the accs
    stay replicated with no extra collective — and returned as outputs
    4..7 for ONE pull at loop exit."""
    from ..ops.packed import PACKED_SENTINEL, level_word, packed_cap
    from ..ops.relay import pack_std, unpack_relay_packed
    from .exchange import ExchangeConfig, make_exchange

    n = mesh.shape[GRAPH_AXIS]
    block, in_classes, packed, expansion = _static_parts(static)
    mxu = expansion[0] == "mxu"
    nw = block // 32
    gtot = n * block
    cap = packed_cap(max_levels) if packed else max_levels
    ex_cfg = ExchangeConfig(*exchange)
    mode = direction[0] if direction is not None else None
    if mode in ("auto", "push") and not sparse:
        # No adjacency operands shipped: the dense relay is the only
        # body.  Normalized here (not silently at the engine) so the
        # recorded schedule stays honest for any direct program caller.
        mode = None
    if mode in ("auto", "push"):
        from ..models.bfs import sparse_budgets

        # STATIC Python values (jit static_argnames tuple members), cast
        # at trace-build time — never a device sync.
        dir_alpha = float(direction[1])  # bfs_tpu: ok TRC002 static tuple member
        dir_beta = float(direction[2])  # bfs_tpu: ok TRC002 static tuple member
        v_real = int(direction[3])  # bfs_tpu: ok TRC002 static tuple member
        e_real = int(direction[4])  # bfs_tpu: ok TRC002 static tuple member
        bv, _ = sparse_budgets(gtot, gtot)
        _, be = sparse_budgets(gtot, adj_dst.shape[-1])
        _, be_pred = sparse_budgets(gtot, e_real)

    def inner(vperm_blk, net_blk, valid_blk, own_all, indptr, adj_d,
              adj_s, outdeg, source):
        vperm_blk = _strip_shard_dim(vperm_blk)
        net_blk = _strip_shard_dim(net_blk)
        valid_blk = valid_blk[0]
        own_local = own_all[jax.lax.axis_index(GRAPH_AXIS)]
        if sparse:
            indptr = indptr[0]
            adj_d = adj_d[0]
            adj_s = adj_s[0]
        fwords = _packed_source_frontier(source, block, n)
        exchange_fn = make_exchange(
            ex_cfg, own_all.shape[1], nw, GRAPH_AXIS
        )

        def cond(c):
            return c["changed"] & (c["level"] < cap)

        if mxu:

            def dense_cand(fw):
                return _mxu_candidates_shard(
                    fw, vperm_blk, expansion=expansion, packed=packed
                )

        else:

            def dense_cand(fw):
                return _relay_candidates_shard(
                    fw, vperm_blk, net_blk, valid_blk, static=static
                )

        def push_cand(fw, unreached):
            return _sharded_push_candidates(
                fw, indptr, adj_d, adj_s, unreached,
                gtot=gtot, block=block, bv=bv, be=be, packed=packed,
            )

        if mode in ("auto", "push"):
            from ..models.direction import frontier_masses_words

            def global_masses(fw):
                # Replicated math on replicated inputs (the all-gathered
                # words + the replicated outdeg table): every shard
                # computes the identical masses, no collective needed to
                # agree on the branch.
                return frontier_masses_words(fw, outdeg, gtot)

            def budget_ok(fsize, fe):
                return (fsize <= bv) & (fe <= jnp.float32(be_pred))

        if telemetry:
            from ..obs import telemetry as T

        def body(c):
            fw, level = c["fw"], c["level"]
            if packed:
                pk = c["pk"]
                unreached = pk == PACKED_SENTINEL
            else:
                dist, parent = c["dist"], c["parent"]
                unreached = dist == INT32_MAX

            # ---- per-superstep body selection (pure replicated math) ----
            if mode == "auto":
                from ..models.direction import take_pull

                fsize, fe = global_masses(fw)
                m_u = jnp.maximum(c["mu"] - fe, 0.0)
                use_pull = (
                    take_pull(
                        c["prev"], fsize, fe, m_u, v_real, dir_alpha,
                        dir_beta,
                    )
                    | ~budget_ok(fsize, fe)
                )
            elif mode == "push":
                fsize, fe = global_masses(fw)
                use_pull = ~budget_ok(fsize, fe)
            else:
                use_pull = None

            if use_pull is None:
                cand = dense_cand(fw)
            else:
                cand = jax.lax.cond(
                    use_pull,
                    dense_cand,
                    lambda f: push_cand(f, unreached),
                    fw,
                )

            # ---- improvement mask + the SIEVE (settled never ships) -----
            level2 = level + 1
            if packed:
                candw = cand | level_word(level2)
                improved = candw < pk
            else:
                improved = (cand != INT32_MAX) & unreached

            # ---- exchange issued BEFORE the state writes (overlap) ------
            fw2, xbytes, xarm = exchange_fn(
                pack_std(improved), own_local, own_all
            )
            changed = (
                jax.lax.pmax(improved.any().astype(jnp.int32), GRAPH_AXIS)
                > 0
            )

            # ---- local state update (flies under the collective) --------
            out = dict(c)
            if packed:
                out["pk"] = jnp.minimum(pk, candw)
            else:
                out["dist"] = jnp.where(improved, level2, dist)
                out["parent"] = jnp.where(improved, cand, parent)
            out["fw"] = fw2
            out["level"] = level2
            out["changed"] = changed
            if mode == "auto":
                out["mu"] = m_u
                out["prev"] = use_pull
            if telemetry:
                out["occ"] = T.record_frontier_words(c["occ"], fw2, level2)
                if use_pull is None:
                    code = jnp.int32(T.DIR_PULL)
                else:
                    code = jnp.where(
                        use_pull, jnp.int32(T.DIR_PULL),
                        jnp.int32(T.DIR_PUSH),
                    )
                out["dirs"] = T.record_direction(c["dirs"], level2, code)
                out["xb"], out["xa"] = T.record_exchange(
                    c["xb"], c["xa"], level2, xbytes, xarm
                )
            return out

        carry = {
            "fw": fwords,
            "level": jnp.int32(0),
            "changed": jnp.bool_(True),
        }
        if packed:
            lo = jax.lax.axis_index(GRAPH_AXIS).astype(jnp.int32) * block
            ids_local = lo + jnp.arange(block, dtype=jnp.int32)
            carry["pk"] = jnp.where(
                ids_local == source, jnp.uint32(0), PACKED_SENTINEL
            )
        else:
            carry["dist"], carry["parent"] = _init_block_state(source, block)
        # Replicated-initialized leaves whose body outputs derive from
        # graph-axis-varying values: cast the init side like the frontier
        # words (compat.pcast_carry — identity on jax 0.4.x).
        extras = {}
        if mode == "auto":
            extras["mu"] = outdeg.astype(jnp.float32).sum()
            extras["prev"] = jnp.bool_(False)
        if telemetry:
            extras["occ"] = T.init_level_acc()
            extras["dirs"] = T.init_dir_acc()
            extras["xb"] = T.init_bytes_acc()
            extras["xa"] = T.init_dir_acc()
        carry.update(pcast_carry(extras, (GRAPH_AXIS,)))

        out = jax.lax.while_loop(cond, body, carry)
        if packed:
            if mxu:
                from ..ops.packed import packed_dist, packed_parent

                dist, parent = packed_dist(out["pk"]), packed_parent(
                    out["pk"]
                )
            else:
                dist, parent = unpack_relay_packed(
                    out["pk"], in_classes, block
                )
        else:
            dist, parent = out["dist"], out["parent"]
        if telemetry:
            return (
                dist, parent, out["level"], out["changed"],
                *unvary(
                    (out["occ"], out["dirs"], out["xb"], out["xa"]),
                    (GRAPH_AXIS,),
                ),
            )
        return dist, parent, out["level"], out["changed"]

    fn = _shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            _mask_specs(vperm_masks),
            _mask_specs(net_masks),
            P(GRAPH_AXIS, None),
            P(),
            P(GRAPH_AXIS, None),
            P(GRAPH_AXIS, None),
            P(GRAPH_AXIS, None),
            P(),
            P(),
        ),
        out_specs=(
            (P(GRAPH_AXIS), P(GRAPH_AXIS), P(), P(), P(), P(), P(), P())
            if telemetry
            else (P(GRAPH_AXIS), P(GRAPH_AXIS), P(), P())
        ),
        # Fully manual over BOTH mesh axes: a partially-manual program (the
        # batch axis left in auto mode) would require the SPMD partitioner
        # to partition the Mosaic custom calls over the auto axis, which it
        # cannot do — even at axis size 1.  The program never communicates
        # over batch; it is simply replicated along it.
        axis_names={GRAPH_AXIS, BATCH_AXIS},
    )
    return fn(
        vperm_masks, net_masks, valid_words, own_words,
        adj_indptr, adj_dst, adj_slot, outdeg, source_new,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "static", "max_levels", "telemetry", "direction",
        "exchange", "sparse",
    ),
)
def _bfs_sharded_relay_segment(
    carry, seg_end, vperm_masks, net_masks, valid_words, own_words,
    adj_indptr, adj_dst, adj_slot, outdeg, *,
    mesh, static, max_levels, telemetry: bool = False,
    direction: tuple | None = None, exchange: tuple = ("bitmap", 8),
    sparse: bool = False,
):
    """ONE bounded segment of the sharded relay loop (ISSUE 14): the
    checkpointable twin of :func:`_bfs_sharded_relay_fused` — identical
    superstep body (same candidate pipelines, sieve, overlapped exchange
    arms, direction cond and telemetry/exchange accumulators), stopped at
    ``seg_end`` supersteps so the host can snapshot the carry at the
    EXCHANGE BOUNDARY (the per-superstep consistency point) and write
    per-shard checkpoint shards.  The carry dict holds the global view of
    every loop leaf: the per-shard state (``pk`` or ``dist``/``parent``,
    shard-major, split over the ``graph`` axis), the replicated global
    frontier words, the direction hysteresis pair and the telemetry /
    exchange-arm accumulators — a snapshot is a complete resume point and
    a resumed run replays the direction schedule AND the exchange-arm
    sequence bit-identically.  NEW lint-registered program; the fused
    off-arm is untouched."""
    from ..ops.packed import PACKED_SENTINEL, level_word, packed_cap
    from ..ops.relay import pack_std
    from .exchange import ExchangeConfig, make_exchange

    n = mesh.shape[GRAPH_AXIS]
    block, _in_classes, packed, expansion = _static_parts(static)
    mxu = expansion[0] == "mxu"
    nw = block // 32
    gtot = n * block
    cap = packed_cap(max_levels) if packed else max_levels
    ex_cfg = ExchangeConfig(*exchange)
    mode = direction[0] if direction is not None else None
    if mode in ("auto", "push") and not sparse:
        mode = None
    if mode in ("auto", "push"):
        from ..models.bfs import sparse_budgets

        dir_alpha = float(direction[1])  # bfs_tpu: ok TRC002 static tuple member
        dir_beta = float(direction[2])  # bfs_tpu: ok TRC002 static tuple member
        v_real = int(direction[3])  # bfs_tpu: ok TRC002 static tuple member
        e_real = int(direction[4])  # bfs_tpu: ok TRC002 static tuple member
        bv, _ = sparse_budgets(gtot, gtot)
        _, be = sparse_budgets(gtot, adj_dst.shape[-1])
        _, be_pred = sparse_budgets(gtot, e_real)

    state_keys = ("pk",) if packed else ("dist", "parent")

    def inner(c, seg_end, vperm_blk, net_blk, valid_blk, own_all, indptr,
              adj_d, adj_s, outdeg):
        vperm_blk = _strip_shard_dim(vperm_blk)
        net_blk = _strip_shard_dim(net_blk)
        valid_blk = valid_blk[0]
        own_local = own_all[jax.lax.axis_index(GRAPH_AXIS)]
        if sparse:
            indptr = indptr[0]
            adj_d = adj_d[0]
            adj_s = adj_s[0]
        exchange_fn = make_exchange(
            ex_cfg, own_all.shape[1], nw, GRAPH_AXIS
        )

        # Replicated-in leaves whose body outputs are graph-axis-varying
        # must be cast on entry, exactly like the fused program's init
        # side (compat.pcast_carry — identity on jax 0.4.x).
        c = dict(c)
        c["fw"] = pcast_varying(c["fw"], (GRAPH_AXIS,))
        extras = {
            k: c[k] for k in ("mu", "prev", "occ", "dirs", "xb", "xa")
            if k in c
        }
        c.update(pcast_carry(extras, (GRAPH_AXIS,)))

        def cond(c):
            return (
                c["changed"] & (c["level"] < cap)
                & (c["level"] < seg_end)
            )

        if mxu:

            def dense_cand(fw):
                return _mxu_candidates_shard(
                    fw, vperm_blk, expansion=expansion, packed=packed
                )

        else:

            def dense_cand(fw):
                return _relay_candidates_shard(
                    fw, vperm_blk, net_blk, valid_blk, static=static
                )

        def push_cand(fw, unreached):
            return _sharded_push_candidates(
                fw, indptr, adj_d, adj_s, unreached,
                gtot=gtot, block=block, bv=bv, be=be, packed=packed,
            )

        if mode in ("auto", "push"):
            from ..models.direction import frontier_masses_words

            def global_masses(fw):
                return frontier_masses_words(fw, outdeg, gtot)

            def budget_ok(fsize, fe):
                return (fsize <= bv) & (fe <= jnp.float32(be_pred))

        if telemetry:
            from ..obs import telemetry as T

        def body(c):
            fw, level = c["fw"], c["level"]
            if packed:
                pk = c["pk"]
                unreached = pk == PACKED_SENTINEL
            else:
                dist, parent = c["dist"], c["parent"]
                unreached = dist == INT32_MAX

            if mode == "auto":
                from ..models.direction import take_pull

                fsize, fe = global_masses(fw)
                m_u = jnp.maximum(c["mu"] - fe, 0.0)
                use_pull = (
                    take_pull(
                        c["prev"], fsize, fe, m_u, v_real, dir_alpha,
                        dir_beta,
                    )
                    | ~budget_ok(fsize, fe)
                )
            elif mode == "push":
                fsize, fe = global_masses(fw)
                use_pull = ~budget_ok(fsize, fe)
            else:
                use_pull = None

            if use_pull is None:
                cand = dense_cand(fw)
            else:
                cand = jax.lax.cond(
                    use_pull,
                    dense_cand,
                    lambda f: push_cand(f, unreached),
                    fw,
                )

            level2 = level + 1
            if packed:
                candw = cand | level_word(level2)
                improved = candw < pk
            else:
                improved = (cand != INT32_MAX) & unreached

            fw2, xbytes, xarm = exchange_fn(
                pack_std(improved), own_local, own_all
            )
            changed = (
                jax.lax.pmax(improved.any().astype(jnp.int32), GRAPH_AXIS)
                > 0
            )

            out = dict(c)
            if packed:
                out["pk"] = jnp.minimum(pk, candw)
            else:
                out["dist"] = jnp.where(improved, level2, dist)
                out["parent"] = jnp.where(improved, cand, parent)
            out["fw"] = fw2
            out["level"] = level2
            out["changed"] = changed
            if mode == "auto":
                out["mu"] = m_u
                out["prev"] = use_pull
            if telemetry:
                out["occ"] = T.record_frontier_words(c["occ"], fw2, level2)
                if use_pull is None:
                    code = jnp.int32(T.DIR_PULL)
                else:
                    code = jnp.where(
                        use_pull, jnp.int32(T.DIR_PULL),
                        jnp.int32(T.DIR_PUSH),
                    )
                out["dirs"] = T.record_direction(c["dirs"], level2, code)
                out["xb"], out["xa"] = T.record_exchange(
                    c["xb"], c["xa"], level2, xbytes, xarm
                )
            return out

        out = jax.lax.while_loop(cond, body, c)
        return {
            k: v if k in state_keys else unvary(v, (GRAPH_AXIS,))
            for k, v in out.items()
        }

    carry_in_specs = {
        k: (P(GRAPH_AXIS) if k in state_keys else P()) for k in carry
    }
    fn = _shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            carry_in_specs,
            P(),
            _mask_specs(vperm_masks),
            _mask_specs(net_masks),
            P(GRAPH_AXIS, None),
            P(),
            P(GRAPH_AXIS, None),
            P(GRAPH_AXIS, None),
            P(GRAPH_AXIS, None),
            P(),
        ),
        out_specs=carry_in_specs,
        axis_names={GRAPH_AXIS, BATCH_AXIS},
    )
    return fn(
        carry, seg_end, vperm_masks, net_masks, valid_words, own_words,
        adj_indptr, adj_dst, adj_slot, outdeg,
    )


@functools.lru_cache(maxsize=8)
def _sharded_segment_unpack_program(in_classes: tuple, block: int, n: int,
                                    mxu: bool = False):
    """Jitted per-shard unpack for the segmented runner's TRUE loop exit
    (cached at module level — a per-call jit would retrace, RCD001).
    The mxu flavor decodes original-id parents (no slot pass)."""
    from ..ops.relay import unpack_relay_packed

    @jax.jit
    def unpack(pk):
        if mxu:
            from ..ops.packed import packed_dist, packed_parent

            p2 = pk.reshape(n, block)
            return packed_dist(p2), packed_parent(p2)
        return jax.vmap(
            lambda p: unpack_relay_packed(p, in_classes, block)
        )(pk.reshape(n, block))

    return unpack


def sharded_segment_keys(packed: bool, auto: bool,
                         telemetry: bool) -> list[str]:
    """The sharded segment carry's key set — the ONE definition
    :func:`sharded_segment_carry` builds from and the restore gate
    validates against."""
    keys = (["pk"] if packed else ["dist", "parent"]) + [
        "fw", "level", "changed",
    ]
    if auto:
        keys += ["mu", "prev"]
    if telemetry:
        keys += ["occ", "dirs", "xb", "xa"]
    return keys


def sharded_segment_carry(srg, n: int, source_new: int, packed: bool,
                          auto: bool, telemetry: bool, outdeg_dev,
                          restore: dict | None = None) -> dict:
    """Initial (or checkpoint-restored) global-view carry for
    :func:`_bfs_sharded_relay_segment`.  ``restore`` maps carry keys to
    host arrays (the reassembled epoch — per-shard state concatenated
    shard-major); metadata keys are ignored."""
    from ..ops.packed import PACKED_SENTINEL

    block = srg.block
    gtot = n * block
    nw = block // 32
    keys = sharded_segment_keys(packed, auto, telemetry)
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
    carry.update(
        fw=jnp.asarray(fw), level=jnp.int32(0), changed=jnp.bool_(True)
    )
    if auto:
        # Same seed as the fused program's replicated init (float32 sum
        # of integer out-degrees — exact below 2^24 edges).
        carry["mu"] = outdeg_dev.astype(jnp.float32).sum()
        carry["prev"] = jnp.bool_(False)
    if telemetry:
        from ..obs import telemetry as T

        carry["occ"] = T.init_level_acc()
        carry["dirs"] = T.init_dir_acc()
        carry["xb"] = T.init_bytes_acc()
        carry["xa"] = T.init_dir_acc()
    return carry


def bfs_sharded_segmented(
    graph,
    source: int = 0,
    *,
    mesh: Mesh | None = None,
    ckpt,
    max_levels: int | None = None,
    applier: str = "auto",
    telemetry: bool = False,
    direction: str | None = None,
    exchange: str | None = None,
    expansion: str | None = None,
):
    """Segmented-with-checkpoints sharded relay BFS (ISSUE 14): the
    resumable twin of :func:`bfs_sharded` ``engine='relay'`` —
    bit-identical dist/parent, direction schedule and exchange-arm
    sequence for any segmentation.  Each segment ends at the exchange
    boundary; the checkpointer writes one epoch = PER-SHARD state shards
    plus a meta file (replicated frontier words, hysteresis, telemetry/
    exchange accumulators).  Shard-loss recovery: epochs are host
    arrays, so the newest COMPLETE epoch re-admits onto any freshly
    built mesh of the same shape — a damaged or missing shard file makes
    that epoch incomplete and the loader falls back to the last complete
    one (or a fresh traversal), counters naming the fallback.

    ``ckpt`` must be a :class:`~bfs_tpu.resilience.superstep_ckpt.
    SuperstepCheckpointer` built with ``shards == mesh graph axis``."""
    import time as _time

    from ..models.direction import resolve_direction
    from ..ops.packed import (
        PACKED_MAX_LEVELS,
        packed_cap,
        packed_rank_fits,
        packed_truncated,
        resolve_packed,
    )
    from .exchange import resolve_exchange

    mesh = mesh if mesh is not None else make_mesh()
    dir_cfg = resolve_direction(direction)
    ex_cfg = resolve_exchange(exchange)
    srg = _prepare_relay(graph, mesh)
    n = _graph_shards(mesh)
    if getattr(ckpt, "shards", 1) != n:
        raise ValueError(
            f"checkpointer built for {getattr(ckpt, 'shards', 1)} shards "
            f"but the mesh graph axis has {n}"
        )
    check_sources(srg.num_vertices, source)
    max_levels = (
        int(max_levels) if max_levels is not None else srg.num_vertices
    )
    source_new = int(srg.old2new[source])
    use_pallas = _resolve_sharded_applier(applier)
    block = srg.block
    has_adj = srg.adj_dst is not None and srg.outdeg is not None
    if dir_cfg.mode == "push" and not has_adj:
        raise ValueError(
            "direction='push' needs the per-shard adjacency this "
            "ShardedRelayGraph predates"
        )
    sparse = has_adj and dir_cfg.mode in ("auto", "push")
    auto = sparse and dir_cfg.mode == "auto"
    direction_static = (
        dir_cfg.mode, dir_cfg.alpha, dir_cfg.beta,
        srg.num_vertices, srg.num_edges,
    )
    outdeg_dev = (
        jnp.asarray(srg.outdeg) if sparse else jnp.zeros((1,), jnp.int32)
    )
    # Loop-invariant operands hoisted OUT of the segment loop (the fused
    # path builds them once per call; rebuilding the valid-words table per
    # segment would both waste an O(n*net_size) host pass + upload per
    # superstep and inflate the measured superstep seconds the Young/Daly
    # interval is derived from).
    packed0 = resolve_packed(packed_rank_fits(srg.in_classes))
    exp_static, packed0 = _resolve_sharded_expansion(expansion, srg, packed0)
    mxu = exp_static[0] == "mxu"
    if mxu:
        vperm_arg = _sharded_tiles_dev(srg)[0]
        net_arg = jnp.zeros((n, 1), jnp.uint32)
        valid_dev = jnp.zeros((n, 1), jnp.uint32)
    else:
        vperm_arg, net_arg = _sharded_relay_mask_args(srg, use_pallas)
        valid_dev = _relay_valid_words(srg)
    own_dev = _own_word_table_dev(srg)

    def run_flavor(packed: bool):
        static = _sharded_relay_static(srg, n, use_pallas, packed, exp_static)
        adj = (
            _sharded_adj_dev(srg, packed, mxu) if sparse
            else _sharded_adj_dummies(n)
        )
        cap = packed_cap(max_levels) if packed else max_levels
        state_keys = ("pk",) if packed else ("dist", "parent")
        from ..resilience.superstep_ckpt import restore_arrays

        meta_arrays, shard_arrays = restore_arrays(
            ckpt, packed,
            require=tuple(
                k for k in sharded_segment_keys(packed, auto, telemetry)
                if k not in state_keys
            ),
            require_shards=state_keys,
        )
        restore = None
        if meta_arrays is not None:
            # Re-admit the surviving epoch: per-shard state shards
            # reassemble shard-major into the global carry view.
            restore = dict(meta_arrays)
            for k in state_keys:
                restore[k] = np.concatenate([sa[k] for sa in shard_arrays])
        carry = sharded_segment_carry(
            srg, n, source_new, packed, auto, telemetry, outdeg_dev,
            restore=restore,
        )
        level, changed = jax.device_get((carry["level"], carry["changed"]))
        while bool(changed) and int(level) < cap:
            seg_end = jax.device_put(
                np.int32(min(int(level) + ckpt.interval(), cap))
            )
            t0 = _time.perf_counter()
            carry = _bfs_sharded_relay_segment(
                carry, seg_end, vperm_arg, net_arg, valid_dev, own_dev,
                *adj, outdeg_dev,
                mesh=mesh, static=static, max_levels=max_levels,
                telemetry=telemetry, direction=direction_static,
                exchange=ex_cfg.key(), sparse=sparse,
            )
            new_level, changed = jax.device_get(
                (carry["level"], carry["changed"])
            )
            seg_s = _time.perf_counter() - t0
            # Disabled store: mark the boundary, skip the O(V) pull.
            meta_arrays, shard_arrays = {}, []
            if ckpt.enabled:
                host = {k: np.asarray(v) for k, v in
                        jax.device_get(carry).items()}
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
        # The once-per-run unpack at the TRUE end, per shard block (the
        # same per-shard math the fused program runs at its loop exit).
        if packed:
            dist, parent = _sharded_segment_unpack_program(
                tuple(srg.in_classes), block, n, mxu
            )(carry["pk"])
            dist = jax.device_get(dist).reshape(-1)
            parent = jax.device_get(parent).reshape(-1)
        else:
            dist = np.asarray(jax.device_get(carry["dist"]))
            parent = np.asarray(jax.device_get(carry["parent"]))
        return carry, dist, parent, int(level), bool(changed)

    packed = packed0
    carry, dist, parent, level, changed = run_flavor(packed)
    if packed and packed_truncated(changed, level, max_levels):
        ckpt.clear()
        carry, dist, parent, level, changed = run_flavor(False)
        packed = False
    dist, parent = _relay_map_back(
        srg, dist, parent, source, "mxu" if mxu else "gather"
    )
    result = BfsResult(dist=dist, parent=parent, num_levels=level)
    ckpt.clear()
    if not telemetry:
        return result
    from ..obs.telemetry import (
        direction_schedule,
        level_curve,
        read_telemetry,
    )
    from .exchange import exchange_report

    fv, dirs, xb, xa = read_telemetry(
        (carry["occ"], carry["dirs"], carry["xb"], carry["xa"])
    )
    cap = min(PACKED_MAX_LEVELS, max_levels) if packed else max_levels
    curve = level_curve(fv, cap=cap)
    curve["direction_schedule"] = direction_schedule(
        dirs, mode=dir_cfg.mode, alpha=dir_cfg.alpha, beta=dir_cfg.beta
    )
    curve["exchange"] = exchange_report(
        xb, xa, ex_cfg, int(own_dev.shape[1]),
        block // 32, n, num_levels=result.num_levels,
    )
    return result, curve


@functools.partial(
    jax.jit, static_argnames=("mesh", "static", "max_levels")
)
def _bfs_sharded_relay_multi_fused(
    vperm_masks, net_masks, valid_words, own_words, sources_new, *,
    mesh, static, max_levels,
):
    """Batched multi-source relay BFS on a 2-D mesh: sources data-parallel
    over ``batch``, vertices (and the relay pipeline) partitioned over
    ``graph``.  The per-superstep exchange is one frontier-word all-gather
    PER LOCAL TREE; the routing masks are read once per superstep per shard
    and shared by every tree in the local batch (the amortization config 5
    is about).  ``packed`` in ``static`` as in the single-source variant:
    one fused word per (tree, owned vertex), unpacked per tree at exit."""
    from ..ops.packed import PACKED_SENTINEL, level_word, packed_cap
    from ..ops.relay import pack_std, unpack_relay_packed

    n = mesh.shape[GRAPH_AXIS]
    # The batched sharded program is gather-only (the multi twin of the
    # single-chip rule: batch paths run the XLA formulation).
    block, in_classes, packed, _expansion = _static_parts(static)
    nw = block // 32
    cap = packed_cap(max_levels) if packed else max_levels

    def inner(vperm_blk, net_blk, valid_blk, own_all, sources_blk):
        vperm_blk = _strip_shard_dim(vperm_blk)
        net_blk = _strip_shard_dim(net_blk)
        valid_blk = valid_blk[0]
        own_local = own_all[jax.lax.axis_index(GRAPH_AXIS)]
        s_l = sources_blk.shape[0]
        lo = jax.lax.axis_index(GRAPH_AXIS).astype(jnp.int32) * block
        ids_local = lo + jnp.arange(block, dtype=jnp.int32)
        is_src = ids_local[None, :] == sources_blk[:, None]
        fwords = (
            jnp.zeros((s_l, n * nw), jnp.uint32)
            .at[jnp.arange(s_l), sources_blk >> 5]
            .set(jnp.uint32(1) << (sources_blk & 31).astype(jnp.uint32))
        )
        fwords = pcast_varying(fwords, (GRAPH_AXIS,))

        def cond(carry):
            level, changed = carry[-2], carry[-1]
            return changed & (level < cap)

        def candidates(fw):
            return jax.vmap(
                lambda f: _relay_candidates_shard(
                    f, vperm_blk, net_blk, valid_blk, static=static
                )
            )(fw)

        if packed:
            pk0 = jnp.where(is_src, jnp.uint32(0), PACKED_SENTINEL)

            def body(carry):
                pk, fw, level, _ = carry
                cand = candidates(fw)
                pk2 = jnp.minimum(pk, cand | level_word(level + 1))
                improved = pk2 != pk
                fw = _exchange_compact(
                    pack_std(improved), own_local, own_all, nw
                )
                any_local = improved.any().astype(jnp.int32)
                changed = (
                    jax.lax.pmax(
                        jax.lax.pmax(any_local, GRAPH_AXIS), BATCH_AXIS
                    )
                    > 0
                )
                return pk2, fw, level + 1, changed

            pk, _, level, changed = jax.lax.while_loop(
                cond, body, (pk0, fwords, jnp.int32(0), jnp.bool_(True))
            )
            dist, parent = jax.vmap(
                lambda p: unpack_relay_packed(p, in_classes, block)
            )(pk)
            return dist, parent, level, changed

        dist = jnp.where(is_src, jnp.int32(0), INT32_MAX)
        parent = jnp.where(is_src, sources_blk[:, None], jnp.int32(-1))

        def body(carry):
            dist, parent, fw, level, _ = carry
            cand = candidates(fw)
            improved = (cand != INT32_MAX) & (dist == INT32_MAX)
            level = level + 1
            dist = jnp.where(improved, level, dist)
            parent = jnp.where(improved, cand, parent)
            fw = _exchange_compact(pack_std(improved), own_local, own_all, nw)
            any_local = improved.any().astype(jnp.int32)
            changed = (
                jax.lax.pmax(
                    jax.lax.pmax(any_local, GRAPH_AXIS), BATCH_AXIS
                )
                > 0
            )
            return dist, parent, fw, level, changed

        dist, parent, _, level, changed = jax.lax.while_loop(
            cond, body, (dist, parent, fwords, jnp.int32(0), jnp.bool_(True))
        )
        return dist, parent, level, changed

    fn = _shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            _mask_specs(vperm_masks),
            _mask_specs(net_masks),
            P(GRAPH_AXIS, None),
            P(),
            P(BATCH_AXIS),
        ),
        out_specs=(
            P(BATCH_AXIS, GRAPH_AXIS),
            P(BATCH_AXIS, GRAPH_AXIS),
            P(),
            P(),
        ),
        axis_names={GRAPH_AXIS, BATCH_AXIS},
    )
    return fn(vperm_masks, net_masks, valid_words, own_words, sources_new)


def _prepare_relay(graph, mesh: Mesh):
    from ..graph.relay import ShardedRelayGraph, build_sharded_relay_graph

    n = _graph_shards(mesh)
    if isinstance(graph, ShardedPullGraph):
        raise ValueError("a ShardedPullGraph only runs on engine='pull'")
    if isinstance(graph, ShardedRelayGraph):
        if graph.num_shards != n:
            raise ValueError(
                f"ShardedRelayGraph has {graph.num_shards} shards but mesh "
                f"axis '{GRAPH_AXIS}' has {n}; rebuild with num_shards={n}"
            )
        return graph
    return build_sharded_relay_graph(graph, n)


def _own_word_table(srg):
    """Real-word index table for the COMPACT frontier exchange:
    ``int32[n_shards, kw]`` of LOCAL word indices (within each shard's
    ``block/32`` frontier words) that contain at least one real vertex,
    right-padded by repeating the last real index.

    The unified per-shard class structure pads every shard's class counts
    to the max over shards, so the naive ``block``-bit all-gather ships
    padding that GROWS with shard count (+27% at 8 shards on the
    Pokec-shape — VERDICT r4 weak #4).  Gathering only real words keeps
    the exchange flat at ~V/8 bytes: senders gather ``kw`` words through
    this table, receivers scatter them back into the global padded word
    space (pad duplicates rewrite identical values, so the scatter is
    deterministic)."""
    n, block = srg.num_shards, srg.block
    nw = block // 32
    real = (
        (srg.new2old.reshape(n, block) != -1).reshape(n, nw, 32).any(axis=2)
    )
    kw = max(int(real.sum(axis=1).max()), 1)
    rows = []
    for s in range(n):
        idx = np.flatnonzero(real[s]).astype(np.int32)
        if idx.size == 0:
            idx = np.zeros(1, np.int32)
        rows.append(
            np.concatenate([idx, np.full(kw - idx.size, idx[-1], np.int32)])
        )
    return np.stack(rows)


def _own_word_table_dev(srg):
    """Device-resident :func:`_own_word_table`, memoized on the layout
    object: the host table is an O(V) scan + per-shard loop and must not
    land inside a caller's timed repeats (it is layout data, like the
    masks).  ``object.__setattr__`` because ShardedRelayGraph is frozen."""
    cached = getattr(srg, "_own_words_dev", None)
    if cached is None:
        cached = jnp.asarray(_own_word_table(srg))
        object.__setattr__(srg, "_own_words_dev", cached)
    return cached


def _exchange_compact(improved_words, own_local, own_all, nw: int):
    """Compact (bitmap-arm) frontier exchange: local packed words ->
    global packed words, via the ONE bitmap wire-move implementation
    (parallel/exchange.bitmap_gather — the multi-source program and the
    single-source arms must never diverge).  ``improved_words``:
    uint32[..., nw] (this shard's new frontier bits); ``own_local``:
    int32[kw] this shard's real-word indices; ``own_all``: int32[n, kw]
    every shard's table (replicated).  Returns uint32[..., n*nw]."""
    from .exchange import bitmap_gather

    send = jnp.take(improved_words, own_local, axis=-1)
    return bitmap_gather(send, own_all, nw, GRAPH_AXIS)


def _relay_valid_words(srg):
    """Per-shard valid-slot bitmasks (graph/relay.valid_slot_words), stacked
    over shards: uint32[n, net_size/32]."""
    from ..graph.relay import valid_slot_words

    return jnp.asarray(
        np.stack(
            [valid_slot_words(srg.src_l1[s], srg.net_size)
             for s in range(srg.num_shards)]
        )
    )


def _sharded_adj_ranks(srg) -> np.ndarray:
    """Per-edge within-row RANKS from the per-shard adjacency's L1 slots
    (slot = base + rank*stride inverted with the shared local vertex
    tables) — the packed carry's parent-field flavor, derived host-side
    once so the layout stays slot-based (same contract as the single-chip
    engine's ``_adj_ranks``)."""
    from ..graph.relay import _vertex_tables

    base1, stride1 = _vertex_tables(list(srg.in_classes), srg.block)
    d = np.clip(srg.adj_dst, 0, srg.block - 1)
    return (
        (srg.adj_slot - base1[d]) // np.maximum(stride1[d], 1)
    ).astype(np.int32)


def _sharded_adj_dev(srg, packed: bool, mxu: bool = False):
    """Device-resident per-shard adjacency operands ``(indptr, dst,
    slot-or-rank-or-key)``, memoized per flavor on the layout object
    (layout data, like the masks — must not land inside a caller's timed
    repeats).  Raises if this layout predates per-shard adjacency."""
    if srg.adj_dst is None:
        raise ValueError(
            "this ShardedRelayGraph ships no per-shard adjacency "
            "(pre-exchange layout); rebuild with build_sharded_relay_graph"
        )
    key = "_adj_dev_ranks" if packed else "_adj_dev_slots"
    if mxu:
        key = "_adj_dev_keys"
    cached = getattr(srg, key, None)
    if cached is None:
        if mxu:
            third = _sharded_adj_keys(srg)
        else:
            third = _sharded_adj_ranks(srg) if packed else srg.adj_slot
        cached = (
            jnp.asarray(srg.adj_indptr),
            jnp.asarray(srg.adj_dst),
            jnp.asarray(third),
        )
        object.__setattr__(srg, key, cached)
    return cached


def _sharded_adj_dummies(n: int):
    """1-element traced-and-dropped adjacency stand-ins for the dense-only
    program flavors (mirrors RelayEngine's hybrid-off dummies: the fused
    program keeps ONE signature, XLA drops the unused operands)."""
    return (
        jnp.zeros((n, 1), jnp.int32),
        jnp.zeros((n, 1), jnp.int32),
        jnp.zeros((n, 1), jnp.int32),
    )


def _sharded_adj_keys(srg) -> np.ndarray:
    """Per-edge ORIGINAL src ids (the mxu arm's sparse-path payload):
    ``src_l1[shard][slot]`` per shard — sorting (dst, key) is the
    canonical tie-break, the single-chip ``_adj_keys`` contract."""
    slots = np.clip(srg.adj_slot, 0, srg.src_l1.shape[1] - 1)
    shard = np.arange(srg.adj_slot.shape[0])[:, None]
    return np.where(
        srg.adj_slot >= 0, srg.src_l1[shard, slots], srg.adj_slot
    ).astype(np.int32)


def _sharded_tiles_dev(srg):
    """Stacked per-shard MXU tile operands
    (:func:`~bfs_tpu.ops.relay_mxu.mxu_operand_arrays`, leading shard
    axis, per-shard tile counts padded to the max with inert tiles) + the shared static geometry —
    memoized on the layout object like the adjacency flavors."""
    cached = getattr(srg, "_mxu_tiles_dev", None)
    if cached is not None:
        return cached
    from ..graph.adj_tiles import TILE, TILE_WORDS, build_adj_tiles_sharded
    from ..ops.relay_mxu import mxu_operand_arrays, tiles_budget_bytes

    per = build_adj_tiles_sharded(srg, budget_bytes=tiles_budget_bytes())
    ntp = max(at.ntp for at in per)

    def pad(at):
        k = ntp - at.ntp
        if not k:
            return at.tiles, at.row_idx, at.col_id
        return (
            np.concatenate(
                [at.tiles, np.zeros((k, TILE, TILE_WORDS), np.uint32)]
            ),
            np.concatenate(
                [at.row_idx, np.full(k, at.rtp // TILE, np.int32)]
            ),
            np.concatenate(
                [at.col_id, np.full(k, at.vtp // TILE, np.int32)]
            ),
        )

    shards = [
        mxu_operand_arrays(*pad(at), at.sb_indptr, at.keys2d, rtp=at.rtp,
                           vtp=at.vtp)
        for at in per
    ]
    ops = tuple(jnp.asarray(np.stack(f)) for f in zip(*shards))
    geo = (per[0].rows, per[0].cols, per[0].rtp, per[0].vtp, ntp)
    cached = (ops, geo)
    object.__setattr__(srg, "_mxu_tiles_dev", cached)
    return cached


def _resolve_sharded_expansion(expansion, srg, packed: bool):
    """The sharded expansion-arm resolution: forced modes only — 'auto'
    runs gather (the mesh program is AOT-compiled once; the single-chip
    probe's verdict is the measured signal, and the first TPU window
    re-probes).  Returns ``(expansion_static, packed)``; forcing mxu with
    a forced packed carry that cannot hold original ids is an error."""
    import os

    from ..ops.packed import packed_parent_fits
    from ..ops.relay_mxu import resolve_expansion, resolve_mxu_kernel

    req = resolve_expansion(expansion)
    if req != "mxu":
        return ("gather",), packed
    if srg.adj_dst is None:
        raise ValueError(
            "BFS_TPU_EXPANSION=mxu needs the per-shard adjacency this "
            "ShardedRelayGraph predates (the tile builder reads it); "
            "rebuild with build_sharded_relay_graph"
        )
    if packed and not packed_parent_fits(srg.num_vertices):
        if knobs.get("BFS_TPU_PACKED") == "1":
            raise ValueError(
                "BFS_TPU_EXPANSION=mxu with BFS_TPU_PACKED=1 needs "
                "V <= 2^26: the mxu packed parent field carries "
                "ORIGINAL ids"
            )
        packed = False
    _, geo = _sharded_tiles_dev(srg)
    use_kernel = resolve_mxu_kernel() == "pallas"
    return ("mxu", geo, use_kernel), packed


def _relay_map_back(srg, dist, parent, source_or_sources,
                    expansion: str = "gather"):
    """Global-relabeled sharded state -> original-id arrays.  Parent values
    are per-shard L1 slot indices; vertex at global new id g is owned by
    shard g // block with src table src_l1[shard].  On the mxu arm parent
    VALUES are already original ids — only the index space remaps."""
    dist = np.asarray(dist)
    parent = np.asarray(parent)
    if expansion == "mxu":
        parent = parent.astype(np.int32).copy()
    else:
        shard_of = np.arange(parent.shape[-1]) // srg.block
        slots = np.clip(parent, 0, srg.src_l1.shape[1] - 1)
        parent = np.where(
            parent >= 0, srg.src_l1[shard_of, slots], parent
        ).astype(np.int32)
    dist = dist[..., srg.old2new]
    parent = parent[..., srg.old2new]
    if np.ndim(source_or_sources) == 0:
        parent[int(source_or_sources)] = int(source_or_sources)
    else:
        rows = np.arange(len(source_or_sources))
        parent[rows, source_or_sources] = source_or_sources
    return dist, parent


def _prepare_pull(
    graph: Graph | DeviceGraph | ShardedPullGraph, mesh: Mesh, block_multiple: int
) -> ShardedPullGraph:
    from ..graph.relay import ShardedRelayGraph

    n = _graph_shards(mesh)
    if isinstance(graph, ShardedRelayGraph):
        raise ValueError("a ShardedRelayGraph only runs on engine='relay'")
    if isinstance(graph, ShardedPullGraph):
        if graph.num_shards != n:
            raise ValueError(
                f"ShardedPullGraph has {graph.num_shards} shards but mesh axis "
                f"'{GRAPH_AXIS}' has {n}; rebuild with num_shards={n}"
            )
        return graph
    return build_sharded_pull_graph(graph, n, block_multiple=block_multiple)


def bfs_sharded(
    graph: Graph | DeviceGraph | ShardedPullGraph,
    source: int = 0,
    *,
    mesh: Mesh | None = None,
    engine: str = "pull",
    max_levels: int | None = None,
    block: int = 1024,
    vertex_block_multiple: int = 1024,
    applier: str = "auto",
    telemetry: bool = False,
    direction: str | None = None,
    exchange: str | None = None,
    expansion: str | None = None,
):
    """Single-source BFS sharded over the mesh's ``graph`` axis.

    Engines:
      * ``'relay'`` — per-shard Beneš relay layouts; the gather-free
        TPU-fast formulation, multi-chip.  ``applier='auto'`` runs the
        networks as the fused 3-pass Pallas kernels on TPU backends
        (per-device inside ``shard_map``; sizes permitting) and as the
        per-stage XLA path elsewhere; 'pallas'/'xla' force.
      * ``'pull'`` (default) — vertex-partitioned ELL + bit-packed frontier
        bitmap all-gather; portable multi-chip formulation.
      * ``'push'`` — edge-sharded ``segment_min`` + full candidate `pmin`;
        the direct analogue of the reference's map/shuffle/reduce, kept for
        differential testing.

    ``telemetry`` (relay engine only) carries the per-level occupancy,
    direction-schedule and exchange accumulators through the sharded
    loop (obs/telemetry.py) and returns ``(BfsResult, level_curve)`` —
    one extra replicated pull at exit, the curve carrying
    ``direction_schedule`` and ``exchange`` (bytes-on-the-wire per
    level, per-level arm schedule).

    ``direction`` resolves like the single-chip engine's knob
    (BFS_TPU_DIRECTION; models/direction.py).  With the per-shard
    dst-owned adjacency the sharded builder now ships, every mode runs
    across the mesh: ``'pull'`` is the dense relay pipeline every
    superstep, ``'push'`` the sparse gather body wherever the static
    budgets allow, ``'auto'`` the Beamer predicate — bit-identical
    schedules to the single-chip relay engine for the same graph and
    thresholds.  A prebuilt pre-adjacency layout still runs
    ``'pull'``/``'auto'`` (dense only) and rejects ``'push'``.

    ``exchange`` resolves the frontier-exchange arm
    (BFS_TPU_EXCHANGE; parallel/exchange.py):
    ``auto|bitmap|delta|flat``, flat being the uncompressed oracle.  All
    arms are bit-identical in results; only wire bytes differ.
    """
    from ..models.direction import resolve_direction
    from .exchange import resolve_exchange

    mesh = mesh if mesh is not None else make_mesh()
    if telemetry and engine != "relay":
        raise ValueError("telemetry is carried by the sharded relay engine only")
    dir_cfg = resolve_direction(direction)
    if engine == "relay":
        from ..ops.packed import (
            packed_rank_fits,
            packed_truncated,
            resolve_packed,
        )

        ex_cfg = resolve_exchange(exchange)
        srg = _prepare_relay(graph, mesh)
        check_sources(srg.num_vertices, source)
        max_levels = int(max_levels) if max_levels is not None else srg.num_vertices
        source_new = jnp.int32(int(srg.old2new[source]))
        use_pallas = _resolve_sharded_applier(applier)
        n = _graph_shards(mesh)
        has_adj = srg.adj_dst is not None
        if dir_cfg.mode == "push" and not has_adj:
            raise ValueError(
                "direction='push' needs the per-shard adjacency this "
                "ShardedRelayGraph predates; rebuild it with "
                "build_sharded_relay_graph (use 'pull' or 'auto' to run "
                "dense-only)"
            )
        sparse = has_adj and dir_cfg.mode in ("auto", "push")
        direction_static = (
            dir_cfg.mode, dir_cfg.alpha, dir_cfg.beta,
            srg.num_vertices, srg.num_edges,
        )
        outdeg_dev = (
            jnp.asarray(srg.outdeg)
            if sparse and srg.outdeg is not None
            else jnp.zeros((1,), jnp.int32)
        )
        sparse = sparse and srg.outdeg is not None
        packed0 = resolve_packed(packed_rank_fits(srg.in_classes))
        exp_static, packed0 = _resolve_sharded_expansion(
            expansion, srg, packed0
        )
        mxu = exp_static[0] == "mxu"
        if mxu:
            # The tile tuple rides the vperm mask-operand slot (the
            # single-chip trick: one program signature, two arms); the
            # Beneš masks — multi-GB at bench scale — are never even
            # built on this arm, and the valid words become dummies.
            vperm_arg = _sharded_tiles_dev(srg)[0]
            net_arg = jnp.zeros((n, 1), jnp.uint32)
        else:
            vperm_arg, net_arg = _sharded_relay_mask_args(srg, use_pallas)

        def run_prog(packed: bool):
            static = _sharded_relay_static(
                srg, n, use_pallas, packed, exp_static
            )
            adj = (
                _sharded_adj_dev(srg, packed, mxu)
                if sparse
                else _sharded_adj_dummies(n)
            )
            valid_arg = (
                jnp.zeros((n, 1), jnp.uint32)
                if mxu
                else _relay_valid_words(srg)
            )
            args = (
                vperm_arg, net_arg, valid_arg,
                _own_word_table_dev(srg), *adj, outdeg_dev, source_new,
            )
            kwargs = dict(
                mesh=mesh, static=static, max_levels=max_levels,
                telemetry=telemetry, direction=direction_static,
                exchange=ex_cfg.key(), sparse=sparse,
            )
            if use_pallas:
                from ..models.bfs import RelayEngine

                key = ("single", static, mesh, max_levels, telemetry,
                       direction_static, ex_cfg.key(), sparse)
                compiled = _SHARDED_AOT_CACHE.get(key)
                if compiled is None:
                    compiled = _bfs_sharded_relay_fused.lower(
                        *args, **kwargs
                    ).compile(compiler_options=RelayEngine._COMPILER_OPTIONS)
                    while len(_SHARDED_AOT_CACHE) >= _SHARDED_AOT_CACHE_MAX:
                        _SHARDED_AOT_CACHE.pop(next(iter(_SHARDED_AOT_CACHE)))
                    _SHARDED_AOT_CACHE[key] = compiled
                return compiled(*args)
            return _bfs_sharded_relay_fused(*args, **kwargs)

        packed = packed0
        out = run_prog(packed)
        dist, parent, level, changed = out[:4]
        if packed and packed_truncated(
            jax.device_get(changed), jax.device_get(level), max_levels
        ):
            # Deeper than the packed level field: re-run unpacked (same
            # contract as the single-chip engine and elem mode).
            out = run_prog(False)
            dist, parent, level, changed = out[:4]
            packed = False
        dist, parent = _relay_map_back(
            srg, jax.device_get(dist), jax.device_get(parent), source,
            "mxu" if mxu else "gather",
        )
        result = BfsResult(dist=dist, parent=parent, num_levels=int(level))
        if not telemetry:
            return result
        from ..obs.telemetry import (
            direction_schedule,
            level_curve,
            read_telemetry,
        )
        from ..ops.packed import PACKED_MAX_LEVELS
        from .exchange import exchange_report

        fv, dirs, xb, xa = read_telemetry(
            (out[4], out[5], out[6], out[7])
        )
        cap = min(PACKED_MAX_LEVELS, max_levels) if packed else max_levels
        curve = level_curve(fv, cap=cap)
        curve["direction_schedule"] = direction_schedule(
            dirs, mode=dir_cfg.mode, alpha=dir_cfg.alpha, beta=dir_cfg.beta
        )
        curve["exchange"] = exchange_report(
            xb, xa, ex_cfg, int(_own_word_table_dev(srg).shape[1]),
            srg.block // 32, n, num_levels=result.num_levels,
        )
        return result, curve
    if engine == "pull":
        spg = _prepare_pull(graph, mesh, vertex_block_multiple)
        check_sources(spg.num_vertices, source)
        max_levels = int(max_levels) if max_levels is not None else spg.num_vertices
        from ..graph.ell import device_ell_sharded

        ell0_t, folds_t = device_ell_sharded(spg)
        dist, parent, level = _bfs_sharded_pull_fused(
            ell0_t,
            folds_t,
            jnp.int32(source),
            mesh=mesh,
            block=spg.block,
            max_levels=max_levels,
        )
        return BfsResult(
            dist=np.asarray(jax.device_get(dist))[: spg.num_vertices],
            parent=np.asarray(jax.device_get(parent))[: spg.num_vertices],
            num_levels=int(level),
        )
    if engine != "push":
        raise ValueError(
            f"unknown engine {engine!r}; use 'relay', 'pull' or 'push'"
        )
    _reject_wrong_layout_for_push(graph)
    dg = _prepare(graph, mesh, block)
    check_sources(dg.num_vertices, source)
    max_levels = int(max_levels) if max_levels is not None else dg.num_vertices
    state = _bfs_sharded_fused(
        jnp.asarray(dg.src).reshape(dg.num_shards, -1),
        jnp.asarray(dg.dst).reshape(dg.num_shards, -1),
        jnp.int32(source),
        mesh=mesh,
        num_vertices=dg.num_vertices,
        max_levels=max_levels,
    )
    state = jax.device_get(state)
    return BfsResult(
        dist=np.asarray(state.dist[: dg.num_vertices]),
        parent=np.asarray(state.parent[: dg.num_vertices]),
        num_levels=int(state.level),
    )


@functools.partial(
    jax.jit, static_argnames=("mesh", "num_vertices", "max_levels")
)
def _bfs_sharded_multi_fused(src, dst, sources, *, mesh, num_vertices, max_levels):
    def inner(src_blk, dst_blk, sources_blk):
        src_e = src_blk.reshape(-1)
        dst_e = dst_blk.reshape(-1)
        state = init_batched_state(num_vertices, sources_blk)

        def cond(s: BfsState):
            return s.changed & (s.level < max_levels)

        def body(s: BfsState):
            return relax_superstep_batched(
                s, src_e, dst_e, axis_name=GRAPH_AXIS, batch_axis_name=BATCH_AXIS
            )

        return jax.lax.while_loop(cond, body, state)

    fn = _shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(GRAPH_AXIS, None), P(GRAPH_AXIS, None), P(BATCH_AXIS)),
        out_specs=BfsState(
            P(BATCH_AXIS, None), P(BATCH_AXIS, None), P(BATCH_AXIS, None), P(), P()
        ),
        axis_names={GRAPH_AXIS, BATCH_AXIS},
    )
    return fn(src, dst, sources)


@functools.partial(jax.jit, static_argnames=("mesh", "block", "max_levels"))
def _bfs_sharded_pull_multi_fused(ell0, folds, sources, *, mesh, block, max_levels):
    """Batched multi-source pull BFS on a 2-D mesh: sources data-parallel
    over ``batch``, vertices partitioned over ``graph``.  State is sharded
    over BOTH axes — [S/nb, block] per device — so per-chip memory scales as
    S·V/(nb·n); the per-superstep exchange stays the bit-packed frontier
    all-gather, one bitmap per local source."""
    n = mesh.shape[GRAPH_AXIS]
    vtot = n * block
    nw = block // 32

    def inner(ell0_blk, folds_blk, sources_blk):
        ell0_blk = ell0_blk[0]
        folds_blk = tuple(f[0] for f in folds_blk)
        s_l = sources_blk.shape[0]
        lo = jax.lax.axis_index(GRAPH_AXIS).astype(jnp.int32) * block
        ids_local = lo + jnp.arange(block, dtype=jnp.int32)
        is_src = ids_local[None, :] == sources_blk[:, None]
        dist = jnp.where(is_src, jnp.int32(0), INT32_MAX)
        parent = jnp.where(is_src, sources_blk[:, None], jnp.int32(-1))
        fwords = (
            jnp.zeros((s_l, n * nw), jnp.uint32)
            .at[jnp.arange(s_l), sources_blk >> 5]
            .set(jnp.uint32(1) << (sources_blk & 31).astype(jnp.uint32))
        )
        # See the single-source variant: the all_gather in the body makes
        # the frontier carry graph-axis-varying.
        fwords = pcast_varying(fwords, (GRAPH_AXIS,))
        gids = jnp.arange(vtot, dtype=jnp.int32)
        inf1 = jnp.full((s_l, 1), INT32_MAX, dtype=jnp.int32)

        def cond(carry):
            _, _, _, level, changed = carry
            return changed & (level < max_levels)

        def body(carry):
            dist, parent, fwords, level, _ = carry
            bits = unpack_frontier_blocks(fwords, n, nw)
            ftab_ext = jnp.concatenate(
                [jnp.where(bits, gids[None, :], INT32_MAX), inf1], axis=-1
            )
            cand = pull_candidates_rows(ftab_ext, ell0_blk, folds_blk, block)
            improved = (cand != INT32_MAX) & (dist == INT32_MAX)
            level = level + 1
            dist = jnp.where(improved, level, dist)
            parent = jnp.where(improved, cand, parent)
            fwords = jax.lax.all_gather(
                pack_frontier_block(improved, nw), GRAPH_AXIS, tiled=True, axis=1
            )
            any_local = improved.any().astype(jnp.int32)
            changed = jax.lax.pmax(
                jax.lax.pmax(any_local, GRAPH_AXIS), BATCH_AXIS
            ) > 0
            return dist, parent, fwords, level, changed

        dist, parent, _, level, _ = jax.lax.while_loop(
            cond, body, (dist, parent, fwords, jnp.int32(0), jnp.bool_(True))
        )
        return dist, parent, level

    fn = _shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            P(GRAPH_AXIS, None, None),
            tuple(P(GRAPH_AXIS, None, None) for _ in folds),
            P(BATCH_AXIS),
        ),
        out_specs=(P(BATCH_AXIS, GRAPH_AXIS), P(BATCH_AXIS, GRAPH_AXIS), P()),
        axis_names={GRAPH_AXIS, BATCH_AXIS},
    )
    return fn(ell0, folds, sources)


def bfs_sharded_multi(
    graph: Graph | DeviceGraph | ShardedPullGraph,
    sources,
    *,
    mesh: Mesh | None = None,
    engine: str = "pull",
    max_levels: int | None = None,
    block: int = 1024,
    vertex_block_multiple: int = 1024,
) -> MultiBfsResult:
    """Batched multi-source BFS: sources sharded over ``batch`` (DP), the
    graph over ``graph`` (the context-parallel analogue).  Sources count must
    be a multiple of the batch axis size.  ``engine`` as in
    :func:`bfs_sharded`."""
    mesh = mesh if mesh is not None else make_mesh()
    sources = np.atleast_1d(np.asarray(sources, dtype=np.int32))
    nb = mesh.shape[BATCH_AXIS]
    if sources.shape[0] % nb != 0:
        raise ValueError(f"{sources.shape[0]} sources not divisible by batch axis {nb}")
    if engine == "relay":
        from ..ops.packed import (
            packed_rank_fits,
            packed_truncated,
            resolve_packed,
        )

        srg = _prepare_relay(graph, mesh)
        check_sources(srg.num_vertices, sources)
        max_levels = int(max_levels) if max_levels is not None else srg.num_vertices
        sources_new = jnp.asarray(srg.old2new[sources])

        # The batched variant vmaps the candidate pipeline over local trees;
        # it stays on the per-stage XLA appliers (vmap over the fused Pallas
        # calls is not exercised — the element-major engine is the batched
        # fast path on real hardware, models/bfs.run_multi_elem_device).
        def run_prog(packed: bool):
            return _bfs_sharded_relay_multi_fused(
                jnp.asarray(srg.vperm_masks),
                jnp.asarray(srg.net_masks),
                _relay_valid_words(srg),
                _own_word_table_dev(srg),
                sources_new,
                mesh=mesh,
                static=_sharded_relay_static(
                    srg, _graph_shards(mesh), False, packed
                ),
                max_levels=max_levels,
            )

        packed = resolve_packed(packed_rank_fits(srg.in_classes))
        dist, parent, level, changed = run_prog(packed)
        if packed and packed_truncated(
            jax.device_get(changed), jax.device_get(level), max_levels
        ):
            dist, parent, level, changed = run_prog(False)
        dist, parent = _relay_map_back(
            srg, jax.device_get(dist), jax.device_get(parent), sources
        )
        return MultiBfsResult(
            sources=sources, dist=dist, parent=parent, num_levels=int(level)
        )
    if engine == "pull":
        spg = _prepare_pull(graph, mesh, vertex_block_multiple)
        check_sources(spg.num_vertices, sources)
        max_levels = int(max_levels) if max_levels is not None else spg.num_vertices
        from ..graph.ell import device_ell_sharded

        ell0_t, folds_t = device_ell_sharded(spg)
        dist, parent, level = _bfs_sharded_pull_multi_fused(
            ell0_t,
            folds_t,
            jnp.asarray(sources),
            mesh=mesh,
            block=spg.block,
            max_levels=max_levels,
        )
        v = spg.num_vertices
        return MultiBfsResult(
            sources=sources,
            dist=np.asarray(jax.device_get(dist))[:, :v],
            parent=np.asarray(jax.device_get(parent))[:, :v],
            num_levels=int(level),
        )
    if engine != "push":
        raise ValueError(
            f"unknown engine {engine!r}; use 'relay', 'pull' or 'push'"
        )
    _reject_wrong_layout_for_push(graph)
    dg = _prepare(graph, mesh, block)
    check_sources(dg.num_vertices, sources)
    max_levels = int(max_levels) if max_levels is not None else dg.num_vertices
    state = _bfs_sharded_multi_fused(
        jnp.asarray(dg.src).reshape(dg.num_shards, -1),
        jnp.asarray(dg.dst).reshape(dg.num_shards, -1),
        jnp.asarray(sources),
        mesh=mesh,
        num_vertices=dg.num_vertices,
        max_levels=max_levels,
    )
    state = jax.device_get(state)
    v = dg.num_vertices
    return MultiBfsResult(
        sources=sources,
        dist=np.asarray(state.dist[:, :v]),
        parent=np.asarray(state.parent[:, :v]),
        num_levels=int(state.level),
    )
