"""Packed ``level:6 | parent:26`` state (ops/packed.py): bit-exact parity
vs the host oracle across all engines, the level-overflow sentinel +
fallback chain, per-shard class balance with the asserted padded-work
ratio, and the phase ledger's halved state-update byte accounting."""

import numpy as np
import pytest

import jax.numpy as jnp

from bfs_tpu.graph import benes
from bfs_tpu.graph.csr import Graph
from bfs_tpu.graph.generators import gnm_graph, path_graph, rmat_graph
from bfs_tpu.ops.packed import (
    INT32_MAX,
    PACKED_MAX_LEVELS,
    PACKED_SENTINEL,
    pack_host,
    packed_parent_fits,
    packed_rank_fits,
    packed_truncated,
    unpack_host,
)
from bfs_tpu.oracle.bfs import canonical_bfs, check

needs_native = pytest.mark.skipif(
    not benes.native_available(), reason="native benes router unavailable"
)


# ---- the word format --------------------------------------------------------

def test_pack_unpack_roundtrip_and_sentinel():
    dist = np.array([0, 5, PACKED_MAX_LEVELS, INT32_MAX, 1], np.int32)
    parent = np.array([3, (1 << 26) - 1, 0, 12345, 7], np.int32)
    w = pack_host(dist, parent)
    assert w[3] == PACKED_SENTINEL  # unreached -> lattice top
    d2, p2 = unpack_host(w)
    np.testing.assert_array_equal(d2, dist)
    np.testing.assert_array_equal(
        p2, np.where(dist == INT32_MAX, -1, parent)
    )


def test_packed_word_order_is_lexicographic():
    """level major, parent minor: the min-merge prefers earlier levels and,
    within a level, the smaller parent — the canonical tie-break."""
    a = pack_host(np.array([2], np.int32), np.array([9], np.int32))[0]
    b = pack_host(np.array([3], np.int32), np.array([0], np.int32))[0]
    c = pack_host(np.array([2], np.int32), np.array([4], np.int32))[0]
    assert a < b and c < a and min(a, b, c) == c
    assert min(int(PACKED_SENTINEL), int(a)) == int(a)


def test_truncation_predicate():
    assert packed_truncated(True, PACKED_MAX_LEVELS, 10**6)
    assert not packed_truncated(False, PACKED_MAX_LEVELS, 10**6)
    assert not packed_truncated(True, 3, 10**6)
    # the caller's own max_levels, not the cap, stopped the loop:
    assert not packed_truncated(True, 40, 40)


def test_fits_guards():
    assert packed_parent_fits(1 << 26)
    assert not packed_parent_fits((1 << 26) + 1)


# ---- engine parity: packed vs unpacked vs oracle ----------------------------

@needs_native
def test_relay_packed_matches_unpacked_and_oracle(monkeypatch):
    from bfs_tpu.models.bfs import RelayEngine

    g = rmat_graph(9, 8, seed=5)
    monkeypatch.setenv("BFS_TPU_PACKED", "1")
    eng_p = RelayEngine(g)
    assert eng_p.packed
    monkeypatch.setenv("BFS_TPU_PACKED", "0")
    eng_u = RelayEngine(g)
    assert not eng_u.packed
    for s in (0, 17, 300):
        rp, ru = eng_p.run(s), eng_u.run(s)
        dist, parent = canonical_bfs(g, s)
        np.testing.assert_array_equal(rp.dist, dist)
        np.testing.assert_array_equal(rp.parent, parent)
        np.testing.assert_array_equal(ru.dist, dist)
        np.testing.assert_array_equal(ru.parent, parent)
        assert check(g, rp.dist, rp.parent, s) == []


@needs_native
def test_relay_packed_level_overflow_falls_back():
    """A diameter-69 path exceeds the 6-bit level field: the packed run
    must detect the cap exit and re-run unpacked, bit-exact."""
    from bfs_tpu.models.bfs import RelayEngine

    g = path_graph(70)
    eng = RelayEngine(g)  # sparse_hybrid on: covers the packed sparse path
    assert eng.packed
    r = eng.run(0)
    assert r.dist.tolist() == list(range(70))
    assert check(g, r.dist, r.parent, 0) == []
    # the raw packed program really was capped (sanity on the predicate)
    assert r.num_levels > PACKED_MAX_LEVELS


@needs_native
def test_relay_multi_packed_fallback_chain():
    """elem mode (31-level planes) -> packed vmapped (62) -> unpacked:
    each rung of the fallback chain returns oracle-exact trees."""
    from bfs_tpu.models.bfs import RelayEngine

    g = path_graph(70)
    eng = RelayEngine(g, sparse_hybrid=False)
    sources = np.arange(32, dtype=np.int32)
    mr = eng.run_multi_elem(sources)  # falls all the way back
    for i, s in enumerate(sources):
        dist, parent = canonical_bfs(g, int(s))
        np.testing.assert_array_equal(mr.dist[i], dist)
        np.testing.assert_array_equal(mr.parent[i], parent)


def test_pull_push_packed_deep_graph_falls_back():
    from bfs_tpu.models.bfs import bfs

    g = path_graph(70)
    for engine in ("pull", "push"):
        r = bfs(g, 0, engine=engine)
        assert r.dist.tolist() == list(range(70)), engine
        assert check(g, r.dist, r.parent, 0) == [], engine


def test_multisource_packed_deep_graph_falls_back():
    from bfs_tpu.models.multisource import bfs_multi

    g = path_graph(70)
    mr = bfs_multi(g, [0, 65], engine="pull")
    d0, p0 = canonical_bfs(g, 0)
    d1, p1 = canonical_bfs(g, 65)
    np.testing.assert_array_equal(mr.dist[0], d0)
    np.testing.assert_array_equal(mr.parent[0], p0)
    np.testing.assert_array_equal(mr.dist[1], d1)
    np.testing.assert_array_equal(mr.parent[1], p1)


@needs_native
def test_adj_rank_flavor_inverts_slots():
    """The packed sparse path's per-edge ranks reconstruct the layout's
    slots exactly through the static vertex tables."""
    from bfs_tpu.graph.relay import _vertex_tables, build_relay_graph
    from bfs_tpu.models.bfs import _adj_ranks

    g = rmat_graph(8, 8, seed=3)
    rg = build_relay_graph(g)
    ranks = _adj_ranks(rg)
    base1, stride1 = _vertex_tables(list(rg.in_classes), rg.vr)
    rebuilt = base1[rg.adj_dst] + ranks * stride1[rg.adj_dst]
    np.testing.assert_array_equal(rebuilt, rg.adj_slot)
    widths = np.array([0] * rg.vr)
    for cs in rg.in_classes:
        widths[cs.va : cs.vb] = cs.width
    assert (ranks < widths[rg.adj_dst]).all() and (ranks >= 0).all()


# ---- per-shard class balance (sharded relay) --------------------------------

def _skewed_fixture(v: int = 512):
    """Degrees correlated with vertex id: the upper half has in-degree 16,
    the lower half 1 — a contiguous-id partition concentrates each class
    in half the shards, the exact shape behind the x8 padded-work
    amplification (VERDICT r5 weak #5)."""
    half = v // 2
    dst_hi = np.repeat(np.arange(half, v, dtype=np.int64), 16)
    src_hi = (dst_hi * 7 + np.tile(np.arange(16), half)) % half
    dst_lo = np.arange(half, dtype=np.int64)
    src_lo = (dst_lo * 5 + 3) % v
    src = np.concatenate([src_hi, dst_lo * 0 + src_lo])
    dst = np.concatenate([dst_hi, dst_lo])
    return Graph(v, src.astype(np.int32), dst.astype(np.int32))


def _old_unified_envelope(g, n):
    """The pre-change layout arithmetic: contiguous original-id ownership,
    per-width counts maxed over shards — the baseline the balanced
    partition must beat."""
    from bfs_tpu.graph.relay import (
        _build_classes,
        _class_width,
        _round32,
    )

    v = g.num_vertices
    indeg = np.bincount(g.dst, minlength=v)
    in_w = _class_width(indeg)
    vblock = max((v + n - 1) // n, 1)
    shard_of = np.minimum(np.arange(v) // vblock, n - 1)
    widths = np.unique(in_w)
    counts = np.stack(
        [
            np.bincount(
                np.searchsorted(widths, in_w[shard_of == s]),
                minlength=widths.shape[0],
            )
            for s in range(n)
        ],
        axis=1,
    )
    classes = _build_classes(widths, counts.max(axis=1))
    return _round32(classes[-1].vb), classes[-1].sb  # (block, m1)


@needs_native
def test_sharded_per_shard_classes_shrink_padded_slots():
    """The acceptance assertion: per-shard slot count strictly below the
    unified-max baseline on the skewed fixture, at x2 and x8."""
    from bfs_tpu.graph.relay import build_sharded_relay_graph

    g = _skewed_fixture()
    for n in (2, 8):
        srg = build_sharded_relay_graph(g, n)
        old_block, old_m1 = _old_unified_envelope(g, n)
        assert srg.m1 < old_m1, (n, srg.m1, old_m1)
        assert srg.block <= old_block, (n, srg.block, old_block)
    # monotone padded work: total slots at x8 do not exceed x2's total
    m1_2 = build_sharded_relay_graph(g, 2).m1 * 2
    m1_8 = build_sharded_relay_graph(g, 8).m1 * 8
    assert m1_8 <= 2 * m1_2  # sub-linear blowup, not the old x(n) one


def _simulate_sharded_relay(g, srg, source, packed):
    """Host-side lock-step simulation of the sharded relay program — the
    exact per-shard pipeline (vperm -> broadcast -> net -> masked row-min
    -> state update -> frontier exchange) minus the collectives, so the
    per-shard layouts and BOTH carry flavors are exercised on any jax
    (the shard_map program itself needs a multi-device mesh).  Returns
    original-id (dist, parent) via the real map-back."""
    from bfs_tpu.graph.relay import valid_slot_words
    from bfs_tpu.ops import relay as R
    from bfs_tpu.ops.packed import level_word
    from bfs_tpu.parallel.sharded import _relay_map_back

    n, block = srg.num_shards, srg.block
    nw = block // 32
    src_new = int(srg.old2new[source])
    valid = [
        jnp.asarray(valid_slot_words(srg.src_l1[s], srg.net_size))
        for s in range(n)
    ]
    fw_host = np.zeros(n * nw, np.uint32)
    fw_host[src_new >> 5] |= np.uint32(1) << (src_new & 31)
    fw = jnp.asarray(fw_host)
    if packed:
        pk = [np.full(block, PACKED_SENTINEL, np.uint32) for _ in range(n)]
        pk[src_new // block][src_new % block] = 0
        pk = [jnp.asarray(x) for x in pk]
    else:
        dist = [np.full(block, INT32_MAX, np.int32) for _ in range(n)]
        par = [np.full(block, -1, np.int32) for _ in range(n)]
        dist[src_new // block][src_new % block] = 0
        par[src_new // block][src_new % block] = src_new
        dist = [jnp.asarray(d) for d in dist]
        par = [jnp.asarray(p) for p in par]
    level, changed = 0, True
    while changed and level < PACKED_MAX_LEVELS:
        level += 1
        imp_words, changed = [], False
        for s in range(n):
            zpad = jnp.zeros(srg.vperm_size // 32 - n * nw, jnp.uint32)
            x = jnp.concatenate([fw, zpad])
            y = R.apply_benes_std(
                x, jnp.asarray(srg.vperm_masks[s]), srg.vperm_table,
                srg.vperm_size,
            )
            l2 = R.broadcast_l2(
                y, srg.out_classes, srg.net_size, srg.out_space
            )
            l1 = R.apply_benes_std(
                l2, jnp.asarray(srg.net_masks[s]), srg.net_table,
                srg.net_size,
            )
            if packed:
                cand = R.rowmin_ranks(l1, valid[s], srg.in_classes, block)
                pk2 = jnp.minimum(pk[s], cand | level_word(jnp.int32(level)))
                improved = pk2 != pk[s]
                pk[s] = pk2
            else:
                cand = R.rowmin_candidates(
                    l1, valid[s], srg.in_classes, block
                )
                improved = (cand != INT32_MAX) & (dist[s] == INT32_MAX)
                dist[s] = jnp.where(improved, level, dist[s])
                par[s] = jnp.where(improved, cand, par[s])
            imp_words.append(R.pack_std(improved))
            changed = changed or bool(improved.any())
        fw = jnp.concatenate(imp_words)  # the all-gather, minus the mesh
    if packed:
        pairs = [
            R.unpack_relay_packed(pk[s], srg.in_classes, block)
            for s in range(n)
        ]
        dist = np.concatenate([np.asarray(d) for d, _ in pairs])
        par = np.concatenate([np.asarray(p) for _, p in pairs])
    else:
        dist = np.concatenate([np.asarray(d) for d in dist])
        par = np.concatenate([np.asarray(p) for p in par])
    return _relay_map_back(srg, dist, par, source)


@needs_native
@pytest.mark.parametrize("num_shards", [1, 2, 8])
def test_sharded_relay_packed_parity(num_shards):
    """x1/x2/x8 parity on the balanced per-shard layouts, BOTH carry
    flavors, dist AND parent bit-exact vs the oracle — on the skewed
    fixture plus an R-MAT."""
    from bfs_tpu.graph.relay import build_sharded_relay_graph

    for g, source in ((_skewed_fixture(), 3), (rmat_graph(8, 8, seed=21), 0)):
        srg = build_sharded_relay_graph(g, num_shards)
        d_o, p_o = canonical_bfs(g, source)
        for packed in (False, True):
            d, p = _simulate_sharded_relay(g, srg, source, packed)
            np.testing.assert_array_equal(d, d_o)
            np.testing.assert_array_equal(p, p_o)


@needs_native
@pytest.mark.parametrize("num_shards", [1, 2, 8])
def test_sharded_relay_packed_parity_on_mesh(num_shards):
    """The real shard_map program on the virtual CPU mesh (runs where the
    harness jax has the new mesh API; the simulation twin above covers
    the math everywhere)."""
    from bfs_tpu.parallel.sharded import bfs_sharded, make_mesh

    g = _skewed_fixture()
    mesh = make_mesh(graph=num_shards)
    res = bfs_sharded(g, 3, mesh=mesh, engine="relay")
    d_o, p_o = canonical_bfs(g, 3)
    np.testing.assert_array_equal(res.dist, d_o)
    np.testing.assert_array_equal(res.parent, p_o)


# ---- the ledger's byte accounting -------------------------------------------

@needs_native
def test_phase_ledger_state_bytes_halved():
    """CPU-runnable microbench (acceptance): the ledger measures every
    phase and its analytic accounting shows the dist/parent state-update
    HBM bytes exactly halved vs the unpacked layout."""
    from bfs_tpu.models.bfs import RelayEngine
    from bfs_tpu.profiling import state_update_bytes, superstep_phase_ledger

    g = gnm_graph(400, 3000, seed=2)
    eng = RelayEngine(g, sparse_hybrid=False)
    ledger = superstep_phase_ledger(eng, loops=2, repeats=1)
    for phase in ("vperm", "broadcast", "net_apply", "rowmin",
                  "state_update", "full_superstep"):
        assert np.isfinite(ledger["phases"][phase]["seconds"])
    su = ledger["phases"]["state_update"]
    assert ledger["packed_state"] == eng.packed
    assert su["dist_parent_bytes_ratio"] == 2.0
    pb, ub = su["packed"]["bytes"], su["unpacked"]["bytes"]
    assert ub["dist_parent_read"] == 2 * pb["dist_parent_read"]
    assert ub["dist_parent_written"] == 2 * pb["dist_parent_written"]
    vr = eng.relay_graph.vr
    assert pb == state_update_bytes(vr, True)
    # parity of the packed engine the ledger just profiled
    r = eng.run(0)
    d_o, p_o = canonical_bfs(g, 0)
    np.testing.assert_array_equal(r.dist, d_o)
    np.testing.assert_array_equal(r.parent, p_o)


@needs_native
def test_multi_tree_device_extraction_matches_host():
    """multi_tree_to_original_device (the elem-mode verification path)
    agrees with the host extraction tree-for-tree."""
    import jax

    from bfs_tpu.models.bfs import RelayEngine

    g = rmat_graph(8, 8, seed=9)
    eng = RelayEngine(g, sparse_hybrid=False)
    sources = (np.arange(32, dtype=np.int32) * 5) % g.num_vertices
    state = eng.run_multi_elem_device(sources)
    mr = eng.run_multi_elem(sources)
    for i in (0, 7, 31):
        dist_d, parent_d = eng.multi_tree_to_original_device(
            state, i, int(sources[i])
        )
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(dist_d)), mr.dist[i]
        )
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(parent_d)), mr.parent[i]
        )
