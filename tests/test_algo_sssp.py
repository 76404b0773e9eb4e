"""Weighted SSSP on the semiring substrate (ISSUE 16).

Covers: min-plus supersteps vs the host Dijkstra oracle (dist AND
canonical parents, bit-for-bit) on star/path/gnm/rmat; stored float32
weights (Graph500 kernel 3) against the float32 oracle, bit for bit, with
parents that form a tree where zero weights tie; delta-stepping
bucket invariance (delta in {1, 17, inf, default} -> one fixpoint); the
packed16 (dist:16|parent:16) arm's schedule identity with the unpacked
carry; the truncation canary -> unpacked fallback; fused-vs-segmented
bit-identity incl. the in-process kill/resume chaos smoke; x2/x8
edge-sharded parity; the on-device invariant counters; the semiring
registry / hash-weight / delta-knob contracts; and the push arm (bit for
bit the sweep's carries, its tier predicate) with the per-graph operands
it reads (the out-row view, cached while the edge arrays live).
"""

import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bfs_tpu.algo import (
    DEFAULT_MAX_WEIGHT,
    SEMIRINGS,
    edge_weights_np,
    resolve_delta,
    sssp,
    sssp_segmented,
    sssp_sharded,
)
from bfs_tpu.algo.sssp import PACKED16_MAX_V, packed16_fits
from bfs_tpu.algo.substrate import edge_weights
from bfs_tpu.graph.csr import INF_DIST, Graph, build_device_graph
from bfs_tpu.graph.generators import (
    gnm_graph,
    path_graph,
    rmat_graph,
    star_graph,
)
from bfs_tpu.oracle import check_sssp, dijkstra, sssp_device_check
from bfs_tpu.ops.relax import dst_ends
from bfs_tpu.resilience import faults
from bfs_tpu.resilience.faults import FaultInjected
from bfs_tpu.resilience.superstep_ckpt import CkptConfig, SuperstepCheckpointer

MAXW = 31
SOURCE = 3

GRAPHS = {
    "star": lambda: star_graph(64),
    "path": lambda: path_graph(200),
    "gnm": lambda: gnm_graph(300, 2100, seed=5),
    "rmat": lambda: rmat_graph(7, 8, seed=2),
}

_cache: dict[str, object] = {}


@pytest.fixture(params=sorted(GRAPHS))
def graph(request):
    if request.param not in _cache:
        _cache[request.param] = GRAPHS[request.param]()
    return _cache[request.param]


def _oracle(graph, source=SOURCE, max_weight=MAXW):
    w = edge_weights_np(graph.src, graph.dst, max_weight)
    return dijkstra(graph, w, source)


def _mgr(tmp_path, k=1, config=None):
    return SuperstepCheckpointer(
        tmp_path, config if config is not None else {"algo": "sssp"},
        cfg=CkptConfig("every", k),
    )


# ------------------------------------------------------------- substrate --
def test_semiring_registry():
    assert set(SEMIRINGS) == {"bfs", "sssp", "cc"}
    # Only valueless contributions ride the AND/popcount MXU tiles.
    assert SEMIRINGS["bfs"].mxu_eligible
    assert not SEMIRINGS["sssp"].mxu_eligible
    assert not SEMIRINGS["cc"].mxu_eligible
    assert SEMIRINGS["bfs"].packable and SEMIRINGS["sssp"].packable
    assert not SEMIRINGS["cc"].packable


def test_edge_weights_host_device_parity(graph):
    w_np = edge_weights_np(graph.src, graph.dst, MAXW)
    w_dev = np.asarray(
        edge_weights(jnp.asarray(graph.src), jnp.asarray(graph.dst), MAXW)
    )
    np.testing.assert_array_equal(w_np, w_dev.astype(w_np.dtype))
    assert int(w_np.min()) >= 1 and int(w_np.max()) <= MAXW


def test_resolve_delta_knob(monkeypatch):
    monkeypatch.delenv("BFS_TPU_SSSP_DELTA", raising=False)
    assert resolve_delta() == 64
    assert resolve_delta(17) == 17
    assert resolve_delta("inf") == 2**31 - 1
    assert resolve_delta(0) == 2**31 - 1
    monkeypatch.setenv("BFS_TPU_SSSP_DELTA", "9")
    assert resolve_delta() == 9
    monkeypatch.setenv("BFS_TPU_SSSP_DELTA", "inf")
    assert resolve_delta() == 2**31 - 1


def test_packed16_gate():
    assert packed16_fits(PACKED16_MAX_V - 1)
    assert not packed16_fits(PACKED16_MAX_V)


# -------------------------------------------------------- oracle parity --
@pytest.mark.algo_smoke
@pytest.mark.parametrize("packed", [False, True])
def test_sssp_matches_dijkstra(graph, packed):
    odist, opar = _oracle(graph)
    res = sssp(graph, SOURCE, max_weight=MAXW, packed=packed)
    np.testing.assert_array_equal(res.dist, odist)
    np.testing.assert_array_equal(res.parent, opar)
    assert res.packed is packed
    assert res.truncated_fallbacks == 0
    w = edge_weights_np(graph.src, graph.dst, MAXW)
    assert check_sssp(graph, w, res.dist, res.parent, SOURCE) == []


@pytest.mark.parametrize("delta", [1, 17, "inf"])
def test_delta_bucket_invariance(graph, delta):
    # Any bucket width reaches the same min-plus fixpoint; parents come
    # from the exit-time canonicalization, so they match too.
    odist, opar = _oracle(graph)
    res = sssp(graph, SOURCE, max_weight=MAXW, delta=delta, packed=False)
    np.testing.assert_array_equal(res.dist, odist)
    np.testing.assert_array_equal(res.parent, opar)


def test_packed_schedule_identity(graph):
    # The packed merge is strict on the dist field, so the frontier
    # schedule — hence the round count — is identical to unpacked.
    r_p = sssp(graph, SOURCE, max_weight=MAXW, packed=True)
    r_u = sssp(graph, SOURCE, max_weight=MAXW, packed=False)
    assert r_p.rounds == r_u.rounds
    np.testing.assert_array_equal(r_p.dist, r_u.dist)
    np.testing.assert_array_equal(r_p.parent, r_u.parent)


@pytest.mark.algo_smoke
def test_packed_truncation_falls_back_unpacked():
    # path(600) x max_weight 255: the true eccentricity overflows 16 bits
    # (the oracle proves the scenario is real), the clamp canary fires,
    # and the driver re-runs unpacked — exact, with the fallback counted.
    g = path_graph(600)
    w = edge_weights_np(g.src, g.dst, DEFAULT_MAX_WEIGHT)
    odist, opar = dijkstra(g, w, 0)
    assert int(odist[odist != INF_DIST].max()) > 0xFFFE
    res = sssp(g, 0, packed=True)
    assert res.packed is False
    assert res.truncated_fallbacks == 1
    np.testing.assert_array_equal(res.dist, odist)
    np.testing.assert_array_equal(res.parent, opar)


# ---------------------------------------------------------- device check --
def test_sssp_device_check(graph):
    res = sssp(graph, SOURCE, max_weight=MAXW, packed=False)
    assert sssp_device_check(
        graph.src, graph.dst, res.dist, res.parent, SOURCE,
        graph.num_vertices, MAXW,
    ) == {}
    bad = res.dist.copy()
    bad[SOURCE] = 1
    viol = sssp_device_check(
        graph.src, graph.dst, bad, res.parent, SOURCE,
        graph.num_vertices, MAXW,
    )
    assert viol.get("source_dist_nonzero") == 1


# ------------------------------------------------- segmented / kill-resume --
@pytest.mark.algo_smoke
@pytest.mark.parametrize("packed", [False, True])
def test_segmented_bit_identical(graph, tmp_path, packed):
    fused = sssp(graph, SOURCE, max_weight=MAXW, packed=packed)
    for k in (2, 3):
        res = sssp_segmented(
            graph, SOURCE, ckpt=_mgr(tmp_path / f"k{k}", k=k),
            max_weight=MAXW, packed=packed,
        )
        np.testing.assert_array_equal(res.dist, fused.dist)
        np.testing.assert_array_equal(res.parent, fused.parent)
        assert res.rounds == fused.rounds
        assert res.packed is fused.packed


def test_segmented_disabled_store_touches_nothing(graph, tmp_path):
    off = SuperstepCheckpointer(
        tmp_path, {"algo": "sssp"}, cfg=CkptConfig("off")
    )
    fused = sssp(graph, SOURCE, max_weight=MAXW, packed=False)
    res = sssp_segmented(
        graph, SOURCE, ckpt=off, max_weight=MAXW, packed=False
    )
    np.testing.assert_array_equal(res.dist, fused.dist)
    assert list(tmp_path.iterdir()) == []


@pytest.mark.chaos
@pytest.mark.parametrize("packed", [False, True])
def test_sssp_kill_resume_bit_identical(tmp_path, packed):
    g = GRAPHS["gnm"]()
    fused = sssp(g, SOURCE, max_weight=MAXW, packed=packed)
    os.environ["BFS_TPU_FAULT"] = "raise:superstep:2"
    faults.reset()
    try:
        with pytest.raises(FaultInjected):
            sssp_segmented(
                g, SOURCE, ckpt=_mgr(tmp_path), max_weight=MAXW,
                packed=packed,
            )
    finally:
        os.environ.pop("BFS_TPU_FAULT", None)
        faults.reset()
    mgr = _mgr(tmp_path)
    res = sssp_segmented(
        g, SOURCE, ckpt=mgr, max_weight=MAXW, packed=packed
    )
    assert mgr.report()["resumed_from_epoch"] == 2
    np.testing.assert_array_equal(res.dist, fused.dist)
    np.testing.assert_array_equal(res.parent, fused.parent)
    assert res.rounds == fused.rounds


# ----------------------------------------------------------------- sharded --
@pytest.mark.algo_smoke
@pytest.mark.parametrize("shards", [2, 8])
def test_sssp_sharded_parity(graph, shards):
    base = sssp(graph, SOURCE, max_weight=MAXW, packed=False)
    res = sssp_sharded(graph, SOURCE, num_shards=shards, max_weight=MAXW)
    np.testing.assert_array_equal(res.dist, base.dist)
    np.testing.assert_array_equal(res.parent, base.parent)
    assert res.rounds == base.rounds


# ------------------------------------------------ stored float32 weights --
# Graph500 kernel 3: one float32 weight per input edge, uniform in [0, 1),
# stored both ways; a repeated edge keeps a weight of its own.

def _weighted_rmat(scale: int, seed: int):
    from bfs_tpu.graph.generators import rmat_edges

    edges = rmat_edges(scale, 16, seed=seed).astype(np.int32)
    w = np.random.default_rng(seed).random(edges.shape[0], dtype=np.float32)
    w[:3] = 0.0  # exact zeros, as jax.random.uniform can draw
    return Graph.from_undirected_edges(1 << scale, edges, weight=w)


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


def _key(graph):
    return int(np.flatnonzero(np.bincount(graph.src,
                                          minlength=graph.num_vertices))[0])


@pytest.mark.parametrize("scale,seed", [(8, 1), (9, 2), (10, 3)])
def test_stored_float_weights_match_float32_dijkstra(scale, seed):
    g = _weighted_rmat(scale, seed)
    pairs = np.stack([g.src, g.dst], axis=1)
    assert (g.src == g.dst).any(), "the graph keeps self-loops"
    assert len(np.unique(pairs, axis=0)) < len(pairs), "and repeated edges"
    root = _key(g)
    odist, _ = dijkstra(g, g.weight, root)
    res = sssp(g, root)
    assert res.dist.dtype == np.float32 and res.max_weight is None
    assert not res.packed and res.delta == np.inf
    np.testing.assert_array_equal(_bits(res.dist), _bits(odist))
    assert np.isinf(res.dist).any() and (res.parent == -1).any()
    assert check_sssp(g, g.weight, res.dist, res.parent, root) == []


@pytest.mark.parametrize("delta", [None, "inf", 0.5, 1.0 / 256])
def test_float_delta_invariance(delta):
    g = _weighted_rmat(9, 4)
    root = _key(g)
    odist, _ = dijkstra(g, g.weight, root)
    res = sssp(g, root, delta=delta)
    np.testing.assert_array_equal(_bits(res.dist), _bits(odist))
    assert check_sssp(g, g.weight, res.dist, res.parent, root) == []


def test_zero_weight_tie_gives_a_tree():
    # root 0; 0-4 and 0-3 of 0.5; 4-1 and 3-2 of 0.25; 1-2 of 0.0: vertices
    # 1 and 2 tie at 0.75, where a least-tight-neighbour rule gives
    # parent[1] = 2 and parent[2] = 1, a cycle.
    edges = np.array([[0, 4], [0, 3], [4, 1], [3, 2], [1, 2]])
    w = np.array([0.5, 0.5, 0.25, 0.25, 0.0], np.float32)
    g = Graph.from_undirected_edges(5, edges, weight=w)
    res = sssp(g, 0)
    np.testing.assert_array_equal(res.dist, [0, 0.75, 0.75, 0.5, 0.5])
    assert res.parent.tolist() == [0, 4, 3, 0, 0]
    assert check_sssp(g, g.weight, res.dist, res.parent, 0) == []
    # An integer zero weight ties the same way.
    gi = Graph.from_undirected_edges(5, edges, weight=(w * 4).astype(np.int32))
    resi = sssp(gi, 0, packed=False)
    assert resi.parent.tolist() == [0, 4, 3, 0, 0]
    assert check_sssp(gi, gi.weight, resi.dist, resi.parent, 0) == []


def test_tie_chain_fixed_in_rounds():
    # A chain of zero-weight edges hangs off one strict parent: each tie
    # round fixes the next vertex out, so the parents follow the chain.
    edges = np.array([[0, 1], [1, 2], [2, 3], [3, 4]])
    w = np.array([0.5, 0.0, 0.0, 0.0], np.float32)
    g = Graph.from_undirected_edges(5, edges, weight=w)
    res = sssp(g, 0)
    assert res.parent.tolist() == [0, 0, 1, 2, 3]
    assert check_sssp(g, g.weight, res.dist, res.parent, 0) == []


def test_device_graph_keeps_each_weight_with_its_edge():
    g = _weighted_rmat(7, 5)
    w = np.arange(g.num_edges, dtype=np.float32)  # a weight names its edge
    g = Graph(g.num_vertices, g.src, g.dst, w)
    dg = build_device_graph(g, block=1000)
    plain = build_device_graph(Graph(g.num_vertices, g.src, g.dst),
                               block=1000)
    np.testing.assert_array_equal(dg.src, plain.src)
    np.testing.assert_array_equal(dg.dst, plain.dst)
    assert dg.weight.shape == dg.src.shape and dg.weight.dtype == np.float32
    real = dg.dst != dg.sentinel
    assert (~real).any(), "the layout is padded"
    idx = dg.weight[real].astype(np.int64)
    np.testing.assert_array_equal(np.sort(idx), np.arange(g.num_edges))
    np.testing.assert_array_equal(g.src[idx], dg.src[real])
    np.testing.assert_array_equal(g.dst[idx], dg.dst[real])
    assert (dg.weight[~real] == 0).all()
    # A sharded layout holds no weights, and sharded SSSP refuses them.
    assert build_device_graph(g, num_shards=2, block=64).weight is None
    with pytest.raises(ValueError, match="one device"):
        sssp_sharded(g, 0, num_shards=2)


@pytest.mark.parametrize("bad", [-0.5, np.nan, np.inf])
def test_bad_weights_raise(bad):
    w = np.ones(4, np.float32)
    w[2] = bad
    edges = np.array([[0, 1], [1, 2], [2, 3], [3, 0]])
    with pytest.raises(ValueError):
        Graph.from_undirected_edges(4, edges, weight=w)
    g = Graph.from_undirected_edges(4, edges)
    with pytest.raises(ValueError):
        dijkstra(g, np.concatenate([w, w]), 0)


def test_weights_must_align_and_be_numbers():
    edges = np.array([[0, 1], [1, 2]])
    with pytest.raises(ValueError):
        Graph.from_undirected_edges(3, edges, weight=np.ones(3, np.float32))
    with pytest.raises(ValueError):
        Graph(3, [0, 1], [1, 2], np.array(["a", "b"]))


def test_stored_int_weights_equal_the_hash_path(graph):
    # Integer weights as an operand: the hash's values stored on the graph
    # give the hash path's numbers, canonical parents included.
    w = edge_weights_np(graph.src, graph.dst, MAXW)
    stored = Graph(graph.num_vertices, graph.src, graph.dst, w)
    res = sssp(stored, SOURCE, delta=64)
    base = sssp(graph, SOURCE, max_weight=MAXW)
    np.testing.assert_array_equal(res.dist, base.dist)
    np.testing.assert_array_equal(res.parent, base.parent)
    assert res.rounds == base.rounds and res.max_weight is None


def test_float_weights_never_take_the_packed_arm():
    g = _weighted_rmat(7, 6)
    assert not sssp(g, _key(g)).packed
    with pytest.raises(ValueError):
        sssp(g, _key(g), packed=True)


def test_float_segmented_bit_identical(tmp_path):
    g = _weighted_rmat(8, 7)
    root = _key(g)
    fused = sssp(g, root, delta=0.25)
    res = sssp_segmented(g, root, ckpt=_mgr(tmp_path, k=2), delta=0.25)
    np.testing.assert_array_equal(_bits(res.dist), _bits(fused.dist))
    np.testing.assert_array_equal(res.parent, fused.parent)
    assert res.rounds == fused.rounds


def test_default_cap_raises_and_explicit_cap_returns(monkeypatch):
    sssp_mod = importlib.import_module("bfs_tpu.algo.sssp")
    g = path_graph(40)
    partial = sssp(g, 0, max_rounds=3, packed=False)
    assert np.count_nonzero(partial.dist != INF_DIST) < 40
    monkeypatch.setattr(sssp_mod, "_rounds_cap", lambda v, m: 3 if m is None else m)
    with pytest.raises(RuntimeError, match="still changing"):
        sssp(g, 0, packed=False)


def test_rounds_cap_is_twice_the_vertices():
    from bfs_tpu.algo.sssp import _rounds_cap

    assert _rounds_cap(100, None) == 202
    assert _rounds_cap(100, 7) == 7
    # A path under delta 1/1024 advances a bucket between most settles:
    # still inside the bound.
    edges = np.stack([np.arange(63), np.arange(1, 64)], axis=1)
    g = Graph.from_undirected_edges(64, edges,
                                    weight=np.ones(63, np.float32))
    assert sssp(g, 0, delta=1.0 / 1024).rounds <= _rounds_cap(64, None)


def test_resolve_delta_by_weights(monkeypatch):
    monkeypatch.delenv("BFS_TPU_SSSP_DELTA", raising=False)
    assert resolve_delta(floating=True) == np.inf
    assert resolve_delta(0.25, floating=True) == 0.25
    assert resolve_delta("0.5", floating=True) == 0.5
    assert resolve_delta(0.25) == 1  # integer weights round a width up
    monkeypatch.setenv("BFS_TPU_SSSP_DELTA", "0.125")
    assert resolve_delta(floating=True) == 0.125
    assert resolve_delta() == 1
    monkeypatch.setenv("BFS_TPU_SSSP_DELTA", "inf")
    assert resolve_delta(floating=True) == np.inf


def test_sssp_spans_and_counters(monkeypatch):
    from bfs_tpu.obs.registry import get_registry
    from bfs_tpu.obs.spans import drain_events

    monkeypatch.setenv("BFS_TPU_SPANS", "1")
    g = _weighted_rmat(7, 8)
    sssp(g, _key(g))  # compiles outside the recorded spans
    drain_events()
    reg = get_registry()
    searches, steps = reg.count("sssp_searches"), reg.count("sssp_supersteps")
    pushes = reg.count("sssp_push_supersteps")
    res = sssp(g, _key(g))
    events = [e for e in drain_events() if e["name"].startswith("sssp.")]
    names = [e["name"] for e in events]
    assert sorted(names) == sorted(["sssp.search", "sssp.launch",
                                    "sssp.parents", "sssp.device_wait",
                                    "sssp.fetch"])
    search = next(e for e in events if e["name"] == "sssp.search")
    assert all(e["parent"] == search["id"] for e in events if e is not search)
    fetch = next(e for e in events if e["name"] == "sssp.fetch")
    n = g.num_vertices + 1
    # dist, parent, rounds, changed, push_rounds
    assert fetch["args"]["bytes"] == 4 * n + 4 * n + 4 + 1 + 4
    assert reg.count("sssp_searches") == searches + 1
    assert reg.count("sssp_supersteps") == steps + res.rounds
    assert reg.count("sssp_push_supersteps") == pushes + res.push_rounds


def test_integer_weights_must_fit_int32():
    edges = np.array([[0, 1]])
    for bad in (2**31, -(2**32) + 5):
        with pytest.raises(ValueError):
            Graph.from_undirected_edges(2, edges,
                                        weight=np.array([bad], np.int64))
    g = Graph.from_undirected_edges(2, edges, weight=np.array([7], np.int64))
    assert g.weight.dtype == np.int32 and g.weight.tolist() == [7, 7]


@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.uint32])
@pytest.mark.parametrize("e,n", [(8, 3), (1000, 40), (4096, 7), (3072, 1),
                                 (640, 200)])
def test_combine_min_scan_form_equals_scatter(dtype, e, n):
    # The scan form (rows of up to 128 edges, tails carried across rows,
    # one gather per destination) gives the scatter's minima exactly:
    # runs that span many rows, destinations with no in-edge (first and
    # last among them), rows narrower than 128 where e is not a multiple.
    from bfs_tpu.ops.relax import combine_min, dst_ends

    rng = np.random.default_rng(e * 31 + n)
    dst = np.sort(rng.integers(1, max(n - 1, 2), e)).astype(np.int32) % n
    dst = np.sort(dst)
    if dtype == np.float32:
        vals = rng.random(e).astype(np.float32)
        vals[rng.random(e) < 0.3] = np.inf
    else:
        vals = rng.integers(0, 1000, e).astype(dtype)
    scatter = combine_min(jnp.asarray(vals), jnp.asarray(dst), n)
    ends = dst_ends(jnp.asarray(dst), n)
    scan = combine_min(jnp.asarray(vals), jnp.asarray(dst), n, ends)
    np.testing.assert_array_equal(np.asarray(scan), np.asarray(scatter))
    assert (np.asarray(ends)[np.bincount(dst, minlength=n) == 0] == e).all()


# ------------------------------------------------------------ push arm --
# The unpacked one-device superstep expands a small frontier's own
# out-edges (the push arm) in place of the sweep over every edge; every
# carry is bit for bit the sweep's.

sssp_mod = importlib.import_module("bfs_tpu.algo.sssp")

#: Tiers small enough that the test graphs take both arms.
TINY_TIERS = ((16, 128), (256, 2048))


def _float_path(n: int):
    edges = np.stack([np.arange(n - 1), np.arange(1, n)], axis=1)
    w = np.random.default_rng(n).random(n - 1, dtype=np.float32)
    return Graph.from_undirected_edges(n, edges, weight=w)


PUSH_GRAPHS = {
    "rmat10": lambda: _weighted_rmat(10, 11),
    "rmat11": lambda: _weighted_rmat(11, 12),
    "rmat12": lambda: _weighted_rmat(12, 13),
    "path": lambda: _float_path(700),
}


def _push_graph(name: str, weights: str):
    key = ("push", name)
    if key not in _cache:
        _cache[key] = PUSH_GRAPHS[name]()
    g = _cache[key]
    if weights == "hash":
        g = Graph(g.num_vertices, g.src, g.dst)
    return g


def _device(graph):
    dg = build_device_graph(graph)
    w = None if dg.weight is None else jnp.asarray(dg.weight)
    return dg.num_vertices, jnp.asarray(dg.src), jnp.asarray(dg.dst), w


def _search(dev, root, *, tiers, delta):
    """One search through the fused program and the parent pass, with
    the push tiers given (none: the sweep every superstep)."""
    v, src, dst, w = dev
    if w is None:
        w = sssp_mod._hash_weights(src, dst, max_weight=MAXW)
    ends, out = sssp_mod._build_operands(src, dst, w, num_slots=v + 1)
    state = sssp_mod._sssp_fused(
        src, dst, w, ends, out, jnp.int32(root),
        num_vertices=v, delta=delta, max_rounds=2 * (v + 1), packed=False,
        tiers=tiers,
    )
    parent = sssp_mod._sssp_parents(state.dist, src, dst, w, ends,
                                    jnp.int32(root), v + 1)
    return (np.asarray(state.dist), np.asarray(parent), int(state.rounds),
            int(state.push_rounds))


def _roots(graph, k=3):
    deg = np.bincount(graph.src, minlength=graph.num_vertices)
    live = np.flatnonzero(deg)
    return [int(live[0]), int(live[len(live) // 2]), int(live[-1])][:k]


@pytest.mark.parametrize("delta", ["inf", "finite"])
@pytest.mark.parametrize("weights", ["float32", "hash"])
@pytest.mark.parametrize("name", sorted(PUSH_GRAPHS))
def test_push_arm_matches_sweep(name, weights, delta):
    g = _push_graph(name, weights)
    dev = _device(g)
    floating = weights == "float32"
    d = resolve_delta(delta if delta == "inf" else (0.25 if floating else 9),
                      floating=floating)
    pushed = 0
    for root in _roots(g):
        ref = _search(dev, root, tiers=(), delta=d)
        got = _search(dev, root, tiers=TINY_TIERS, delta=d)
        assert ref[3] == 0
        np.testing.assert_array_equal(_bits(got[0]) if floating else got[0],
                                      _bits(ref[0]) if floating else ref[0])
        np.testing.assert_array_equal(got[1], ref[1])
        assert got[2] == ref[2]
        assert got[3] <= got[2]
        pushed += got[3]
    assert pushed > 0


@pytest.fixture
def push_on(monkeypatch):
    """Route the public entry points through :data:`TINY_TIERS`."""
    monkeypatch.setattr(sssp_mod, "PUSH_TIERS", TINY_TIERS)
    monkeypatch.setattr(sssp_mod, "PUSH_MIN_SHRINK", 1)


@pytest.mark.parametrize("tiers", [(), ((1, 1),)])
def test_push_rounds_zero_when_budgets_force_the_sweep(push_on, monkeypatch,
                                                       tiers):
    # From a 64-star's hub: the hub (63 out-edges), then its 63 leaves
    # (over the first tier's 16 vertices; the second tier exceeds the
    # graph).  The hub pushes under TINY_TIERS, and sweeps under a budget
    # of one vertex and one edge.
    g = star_graph(64)
    on = sssp(g, 0, delta="inf", max_weight=MAXW, packed=False)
    assert (on.push_rounds, on.rounds) == (1, 2)
    monkeypatch.setattr(sssp_mod, "PUSH_TIERS", tiers)
    off = sssp(g, 0, delta="inf", max_weight=MAXW, packed=False)
    assert off.push_rounds == 0
    np.testing.assert_array_equal(off.dist, on.dist)
    np.testing.assert_array_equal(off.parent, on.parent)
    assert off.rounds == on.rounds


@pytest.mark.parametrize("nf,nd,arm", [
    (4, 16, 0), (4, 17, 1), (4, 64, 1), (4, 65, 2), (5, 1, 2), (0, 0, 0),
])
def test_push_arm_predicate(nf, nd, arm):
    tiers = ((4, 16), (4, 64))
    got = sssp_mod._push_arm(jnp.int32(nf), jnp.int32(nd), tiers)
    assert int(got) == arm


@pytest.mark.parametrize("budget,pushes", [(63, 2), (62, 1)])
def test_hub_over_the_edge_budget_takes_the_sweep(budget, pushes):
    # From a leaf of a 64-star: the leaf (1 out-edge), then the hub (63),
    # then 62 leaves (over the vertex budget).  The hub's superstep pushes
    # where its out-degree is the edge budget and sweeps one above it.
    g = star_graph(64)
    dev = _device(g)
    d = resolve_delta("inf")
    ref = _search(dev, 5, tiers=(), delta=d)
    got = _search(dev, 5, tiers=((4, budget),), delta=d)
    assert got[2] == ref[2] == 3
    assert got[3] == pushes
    np.testing.assert_array_equal(got[0], ref[0])
    np.testing.assert_array_equal(got[1], ref[1])


@pytest.mark.parametrize("weights", ["float32", "hash"])
def test_segmented_equals_fused_with_push(push_on, tmp_path, weights):
    g = _push_graph("rmat10", weights)
    root = _key(g)
    delta = 0.25 if weights == "float32" else 9
    fused = sssp(g, root, delta=delta, max_weight=MAXW, packed=False)
    assert fused.push_rounds > 0
    for k in (1, 2, 5):
        res = sssp_segmented(g, root, ckpt=_mgr(tmp_path / f"k{k}", k=k),
                             delta=delta, max_weight=MAXW, packed=False)
        np.testing.assert_array_equal(res.dist, fused.dist)
        np.testing.assert_array_equal(res.parent, fused.parent)
        assert res.rounds == fused.rounds
        assert res.push_rounds == fused.push_rounds


def test_push_tiers_follow_the_graph():
    tiers, shrink = sssp_mod.PUSH_TIERS, sssp_mod.PUSH_MIN_SHRINK
    # Graph500 scale 20's padded edges keep both tiers.
    assert sssp_mod.push_tiers((1 << 20) + 1, 33_552_384) == tiers
    # The vertex budget clamps to the graph; a tier near the graph's own
    # size would cost more than the sweep, and a small graph has none.
    (bv, be), = sssp_mod.push_tiers(1000, shrink * tiers[0][1])
    assert (bv, be) == (1000, tiers[0][1])
    assert sssp_mod.push_tiers(1000, shrink * tiers[0][1] - 1) == ()


def test_ir_spec_takes_both_arms():
    from bfs_tpu.analysis.ir import PROGRAM_SPECS

    for name in ("algo.sssp_fused", "algo.sssp_fused_float"):
        prog = PROGRAM_SPECS[name]()
        state = prog.fn(*prog.args, **prog.static_kwargs)
        assert 0 < int(state.push_rounds) < int(state.rounds), name


# ----------------------------------------------------- per-graph operands --
def test_out_rows_equal_a_stable_sort_by_source(push_on):
    g = _push_graph("rmat10", "float32")
    v, src, dst, w = _device(g)
    ops = sssp_mod.graph_operands(src, dst, v, weight=w)
    s, d, wn = np.asarray(src), np.asarray(dst), np.asarray(w)
    order = np.argsort(s, kind="stable")
    np.testing.assert_array_equal(np.asarray(ops.out.dst), d[order])
    np.testing.assert_array_equal(_bits(ops.out.w), _bits(wn[order]))
    indptr = np.concatenate([[0], np.cumsum(np.bincount(s, minlength=v + 1))])
    np.testing.assert_array_equal(np.asarray(ops.out.indptr), indptr)
    assert indptr[v] == g.num_edges, "the padding lies in row V"
    np.testing.assert_array_equal(np.asarray(ops.ends),
                                  np.asarray(dst_ends(dst, v + 1)))


def test_second_search_builds_nothing():
    from bfs_tpu.obs.registry import get_registry

    reg = get_registry()
    g = _push_graph("rmat10", "float32")
    v, src, dst, w = _device(g)
    root = _key(g)
    built = reg.count("sssp_operands_built")
    first = sssp_mod.sssp_device(src, dst, v, root, weight=w)
    assert reg.count("sssp_operands_built") == built + 1
    reused = reg.count("sssp_operands_reused")
    second = sssp_mod.sssp_device(src, dst, v, root, weight=w)
    assert reg.count("sssp_operands_built") == built + 1
    assert reg.count("sssp_operands_reused") == reused + 1
    np.testing.assert_array_equal(_bits(second.dist), _bits(first.dist))
    # The hash weights are part of the entry too.
    sssp_mod.sssp_device(src, dst, v, root, max_weight=MAXW)
    sssp_mod.sssp_device(src, dst, v, root, max_weight=MAXW)
    assert reg.count("sssp_operands_built") == built + 2


def test_operands_go_with_their_arrays():
    import gc

    g = _push_graph("rmat10", "float32")
    v, src, dst, w = _device(g)
    held = set(sssp_mod._OPERANDS)
    sssp_mod._kept_operands(src, dst, v, weight=w, max_weight=MAXW, push=True)
    sssp_mod._kept_operands(src, dst, v, weight=None, max_weight=MAXW,
                            push=True)
    assert len(set(sssp_mod._OPERANDS) - held) == 2
    del w
    gc.collect()
    assert len(set(sssp_mod._OPERANDS) - held) == 1, "weights gone: entry gone"
    del src
    gc.collect()
    assert set(sssp_mod._OPERANDS) - held == set()


@pytest.mark.parametrize("case,builds", [
    ("small", 0),  # no push tiers: nothing reads a view
    ("packed", 0),  # the packed carry never pushes
    ("segmented-packed", 0),
    ("unpacked", 1),
    ("segmented-unpacked", 1),
])
def test_out_rows_built_only_where_the_push_arm_reads_them(
        monkeypatch, tmp_path, case, builds):
    g = _push_graph("rmat10", "hash")
    if case != "small":
        monkeypatch.setattr(sssp_mod, "PUSH_TIERS", TINY_TIERS)
        monkeypatch.setattr(sssp_mod, "PUSH_MIN_SHRINK", 1)
    calls = []
    real = sssp_mod._build_operands
    monkeypatch.setattr(sssp_mod, "_build_operands",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    packed = "unpacked" not in case
    if case.startswith("segmented"):
        res = sssp_segmented(g, _key(g), ckpt=_mgr(tmp_path, k=4),
                             max_weight=MAXW, packed=packed)
    else:
        res = sssp(g, _key(g), max_weight=MAXW, packed=packed)
    assert len(calls) == builds
    assert res.packed == packed
    assert (res.push_rounds > 0) == bool(builds)


@pytest.mark.parametrize("push", [True, False])
def test_graph_operands_nbytes_counts_what_is_built(push_on, push):
    g = _push_graph("rmat11", "hash")
    v, src, dst, _ = _device(g)
    ops = sssp_mod.graph_operands(src, dst, v, max_weight=MAXW, push=push)
    assert (ops.out is not None) == push
    held = sum(int(a.nbytes) for a in jax.tree.leaves(ops))
    assert sssp_mod.graph_operands_nbytes(v, src.shape[0], push=push) == held
