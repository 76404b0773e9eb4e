"""The relay engine's host relabel (``relabel_to_original``): the values of
the plain ``slots_to_parent(...)[..., old2new]`` expression, byte for
byte, whatever the number of parts and threads, and the engine's results
through it equal to the CPU oracle with more than one worker."""

from __future__ import annotations

import importlib

import numpy as np
import pytest

from bfs_tpu.graph import benes
from bfs_tpu.graph.csr import Graph
from bfs_tpu.models.bfs import (
    RELABEL_MAX_WORKERS,
    relabel_to_original,
    relabel_workers,
    slots_to_parent,
)
from bfs_tpu.obs.registry import get_registry
from bfs_tpu.oracle.bfs import canonical_bfs

# The package exports the function ``bfs`` under the module's name.
bfs_mod = importlib.import_module("bfs_tpu.models.bfs")
INF = np.iinfo(np.int32).max
#: Original ids, padded relabeled space and L1 slots: 1001 is divisible
#: by none of the part counts below.
V, VR, M1 = 1001, 1056, 4099


def _inputs(rows: int | None, seed: int = 5):
    rng = np.random.default_rng(seed)
    shape = (VR,) if rows is None else (rows, VR)
    dist = rng.integers(0, 40, shape, dtype=np.int32)
    dist[rng.random(shape) < 0.2] = INF
    # Slots past the table clip to its last entry, as the plain
    # expression's np.clip does; -1 is unreached, -7 any other negative.
    parent = rng.integers(0, M1 + 9, shape, dtype=np.int32)
    parent[rng.random(shape) < 0.2] = -1
    parent[rng.random(shape) < 0.05] = -7
    old2new = rng.permutation(VR)[:V].astype(np.int32)
    src_l1 = rng.integers(0, V, M1, dtype=np.int32)
    src_l1[rng.random(M1) < 0.1] = INF
    return dist, parent, old2new, src_l1


@pytest.mark.parametrize("workers", [1, 3, 8])
@pytest.mark.parametrize("slots", [True, False], ids=["gather", "mxu"])
@pytest.mark.parametrize("rows", [None, 1, 2, 5], ids=["1d", "2d1", "2d2", "2d5"])
def test_relabel_matches_plain_expression(rows, slots, workers):
    dist, parent, old2new, src_l1 = _inputs(rows)
    want_dist = dist[..., old2new]
    mapped = slots_to_parent(parent, src_l1) if slots else parent
    want_parent = mapped[..., old2new]
    got_dist, got_parent = relabel_to_original(
        dist, parent, old2new.astype(np.intp), src_l1, slots=slots,
        workers=workers,
    )
    for got, want in ((got_dist, want_dist), (got_parent, want_parent)):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert got.tobytes() == want.tobytes()
    # The inputs are left as they were.
    np.testing.assert_array_equal(dist, _inputs(rows)[0])
    np.testing.assert_array_equal(parent, _inputs(rows)[1])


def test_relabel_takes_int32_indices_and_memmapped_tables(tmp_path):
    """The bundle's tables are int32 and memory-mapped: the same values
    come out when they are passed as they are stored."""
    dist, parent, old2new, src_l1 = _inputs(3)
    np.save(tmp_path / "src_l1.npy", src_l1)
    mapped = np.load(tmp_path / "src_l1.npy", mmap_mode="r")
    got_dist, got_parent = relabel_to_original(
        dist, parent, old2new, mapped, slots=True, workers=4
    )
    assert got_dist.tobytes() == dist[:, old2new].tobytes()
    assert got_parent.tobytes() == (
        slots_to_parent(parent, src_l1)[:, old2new].tobytes())


@pytest.mark.parametrize("rows, v, workers, cores, want", [
    (1, 1 << 15, None, 8, 1),            # small output: inline
    (1, (1 << 16) - 1, None, 8, 1),
    (1, 1 << 16, None, 8, 1),            # one part's worth
    (1, 1 << 20, None, 8, 8),            # capped by the host's cores
    (1, 1 << 20, None, 64, 16),          # and by RELABEL_MAX_WORKERS
    (64, 1 << 20, None, 13, 13),
    (2, 1 << 16, None, 8, 2),            # one part per 2^16 values
    (1, 1 << 20, None, 1, 1),            # a one-core host never splits
    (1, 5, 8, 8, 5),                     # forced: at most one per value
    (3, 100, 3, 1, 3),                   # forced: whatever the host
    (1, 100, 99, 8, RELABEL_MAX_WORKERS),
])
def test_relabel_workers_reads_size_and_host(monkeypatch, rows, v, workers,
                                            cores, want):
    monkeypatch.setattr(bfs_mod, "_host_cores", lambda: cores)
    assert relabel_workers(rows, v, workers) == want


def test_relabel_counts_parallel_runs_only():
    reg = get_registry()
    dist, parent, old2new, src_l1 = _inputs(2)
    before = reg.count("relay_relabel_parallel")
    relabel_to_original(dist, parent, old2new, src_l1, slots=True, workers=1)
    assert reg.count("relay_relabel_parallel") == before
    relabel_to_original(dist, parent, old2new, src_l1, slots=True, workers=3)
    assert reg.count("relay_relabel_parallel") == before + 1


def test_relabel_pool_is_shared():
    assert bfs_mod._relabel_executor() is bfs_mod._relabel_executor()


def test_concurrent_relabels_share_the_pool():
    """Callers on several threads at once, each split over more threads
    than the host has cores, with the interpreter switching often: every
    result is still the plain expression's."""
    import sys
    import threading

    cases = [_inputs(rows, seed=seed) for seed, rows in
             enumerate((None, 3, 5, 2, None, 4))]
    wants = [(d[..., o], slots_to_parent(p, t)[..., o]) for d, p, o, t in cases]
    results = [None] * len(cases)

    def caller(i):
        d, p, o, t = cases[i]
        for _ in range(5):
            results[i] = relabel_to_original(d, p, o, t, slots=True,
                                             workers=RELABEL_MAX_WORKERS)
            if any(g.tobytes() != w.tobytes()
                   for g, w in zip(results[i], wants[i])):
                return

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=caller, args=(i,))
                   for i in range(len(cases))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for got, want in zip(results, wants):
        assert [g.tobytes() for g in got] == [w.tobytes() for w in want]


@pytest.fixture
def split_relabel(monkeypatch):
    """Every engine relabel of more than a few values runs on 4 threads."""
    monkeypatch.setattr(bfs_mod, "RELABEL_INLINE_MAX", 16)
    monkeypatch.setattr(bfs_mod, "_host_cores", lambda: 4)


@pytest.mark.skipif(not benes.native_available(),
                    reason="native benes router unavailable")
@pytest.mark.parametrize("expansion", ["gather", "mxu"])
def test_engine_results_through_parallel_relabel(split_relabel, expansion):
    """``run`` and ``run_multi`` with the relabel split over threads equal
    the CPU oracle, on a graph with an unreached component."""
    from bfs_tpu.models.bfs import RelayEngine

    rng = np.random.default_rng(9)
    # Two extra vertices joined only to each other: unreached from 0..149.
    edges = np.concatenate([rng.integers(0, 150, (420, 2)), [[150, 151]]])
    g = Graph.from_undirected_edges(152, edges)
    eng = RelayEngine(g, expansion=expansion)
    reg = get_registry()
    before = reg.count("relay_relabel_parallel")
    sources = np.array([0, 17, 149], np.int32)
    multi = eng.run_multi(sources)
    single = eng.run(17)
    assert reg.count("relay_relabel_parallel") == before + 2
    for row, s in enumerate(sources):
        dist, parent = canonical_bfs(g, int(s))
        assert multi.dist[row].tobytes() == dist.tobytes()
        assert multi.parent[row].tobytes() == parent.tobytes()
    dist, parent = canonical_bfs(g, 17)
    assert single.dist.tobytes() == dist.tobytes()
    assert single.parent.tobytes() == parent.tobytes()
    assert single.parent[151] == -1 and single.dist[151] == INF
