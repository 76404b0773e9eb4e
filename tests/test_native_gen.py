"""Native data-loader (native/graph_gen.cpp) vs the NumPy fallbacks."""

import os

import numpy as np
import pytest

from bfs_tpu.graph import native_gen
from bfs_tpu.graph.io import read_sedgewick
from conftest import TINY_TEXT

pytestmark = pytest.mark.skipif(
    not native_gen.native_available(), reason="native graph_gen unavailable"
)


def test_rmat_native_shape_range_determinism():
    s1, d1 = native_gen.rmat_edges_native(8, 4, seed=7)
    s2, d2 = native_gen.rmat_edges_native(8, 4, seed=7)
    assert s1.shape == d1.shape == (4 * 256,)
    assert s1.min() >= 0 and s1.max() < 256
    assert d1.min() >= 0 and d1.max() < 256
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(d1, d2)
    s3, _ = native_gen.rmat_edges_native(8, 4, seed=8)
    assert not np.array_equal(s1, s3)


def test_rmat_native_skew():
    # R-MAT graphs are skewed: max degree far above the mean.
    src, dst = native_gen.rmat_edges_native(10, 16, seed=1)
    deg = np.bincount(src, minlength=1 << 10) + np.bincount(dst, minlength=1 << 10)
    assert deg.max() > 8 * deg.mean()


def test_sort_edges_matches_lexsort():
    rng = np.random.default_rng(0)
    src = rng.integers(0, 1000, size=20_000).astype(np.int32)
    dst = rng.integers(0, 1000, size=20_000).astype(np.int32)
    order = np.lexsort((src, dst))
    want_src, want_dst = src[order], dst[order]
    got_src, got_dst = native_gen.sort_edges_by_dst_native(src.copy(), dst.copy())
    np.testing.assert_array_equal(got_src, want_src)
    np.testing.assert_array_equal(got_dst, want_dst)


def test_sedgewick_native_matches_python(tmp_path):
    path = tmp_path / "tiny.txt"
    path.write_text(TINY_TEXT)
    v, src, dst = native_gen.read_sedgewick_native(str(path))
    graph = read_sedgewick(str(path))
    assert v == graph.num_vertices
    # Python reader bi-directs; native returns the raw undirected pairs.
    assert 2 * src.shape[0] == graph.num_edges
    np.testing.assert_array_equal(
        np.sort(np.concatenate([src, dst])), np.sort(np.concatenate([graph.src]))
    )


def test_sedgewick_native_rejects_malformed(tmp_path):
    bad = tmp_path / "bad.txt"
    bad.write_text("6\n8\n0 5\n")  # promises 8 edges, has 1
    with pytest.raises(ValueError):
        native_gen.read_sedgewick_native(str(bad))


def test_rank_by_count_matches_stable_sort_ranks():
    rng = np.random.default_rng(11)
    key = rng.integers(0, 50, 3000).astype(np.int32)
    rank = native_gen.rank_by_count_native(key, 50)
    # rank[i] = number of earlier records with the same key
    want = np.zeros_like(rank)
    seen = {}
    for i, k in enumerate(key.tolist()):
        want[i] = seen.get(k, 0)
        seen[k] = want[i] + 1
    np.testing.assert_array_equal(rank, want)


def test_csr_fill_groups_by_key():
    rng = np.random.default_rng(12)
    n, nk = 5000, 200
    srcn = rng.integers(0, nk, n).astype(np.int32)
    dstn = rng.integers(0, 10_000, n).astype(np.int32)
    slotv = np.arange(n, dtype=np.int32)
    indptr, adj_dst, adj_slot = native_gen.csr_fill_native(srcn, dstn, slotv, nk)
    assert indptr.shape == (nk + 2,)
    assert indptr[nk] == indptr[nk + 1] == n
    for k in range(nk):
        sl = slice(int(indptr[k]), int(indptr[k + 1]))
        # every edge in row k really has key k, and the row is complete
        np.testing.assert_array_equal(srcn[adj_slot[sl]], k)
        np.testing.assert_array_equal(
            np.sort(adj_slot[sl]), np.sort(np.flatnonzero(srcn == k))
        )
        np.testing.assert_array_equal(adj_dst[sl], dstn[adj_slot[sl]])


def test_pad_identity_native_identity_first():
    rng = np.random.default_rng(13)
    n = 4096
    perm = np.full(n, -1, dtype=np.int32)
    # partial mapping: outputs 0..99 <- random distinct inputs 1000..1099
    ins = (1000 + rng.permutation(100)).astype(np.int32)
    perm[:100] = ins
    used = np.zeros(n, dtype=np.uint8)
    native_gen.mark_u8_native(ins, used)
    native_gen.pad_identity_native(perm, used)
    # bijection
    np.testing.assert_array_equal(np.sort(perm), np.arange(n))
    # identity-first: free output j with free input j must map j -> j
    for j in range(100, 1000):
        assert perm[j] == j
    for j in range(1100, n):
        assert perm[j] == j


def test_native_lib_is_keyed_on_source(tmp_path):
    """A library is built under a name keyed on its source and recipe: an
    unkeyed or stale ``.so`` beside it is never loaded, and an edited
    source builds a new library."""
    from bfs_tpu.utils.native_loader import NativeLib

    src = tmp_path / "x.cpp"
    src.write_text('extern "C" int f() { return 1; }\n')
    stale = tmp_path / "build" / "libx.so"
    stale.parent.mkdir()
    stale.write_bytes(b"not a shared library")

    lib = NativeLib(str(src), str(stale), register=lambda lib: None)
    path = lib.so_path()
    assert path != str(stale)
    assert lib.require() is not None and os.path.exists(path)
    src.write_text('extern "C" int f() { return 2; }\n')
    edited = NativeLib(str(src), str(stale), register=lambda lib: None)
    assert edited.so_path() != path


def test_native_lib_build_failure_raises_with_reason(tmp_path):
    from bfs_tpu.utils.native_loader import NativeLib

    src = tmp_path / "bad.cpp"
    src.write_text("this is not C++\n")
    lib = NativeLib(str(src), str(tmp_path / "libbad.so"),
                    register=lambda lib: None)
    assert lib.load() is None
    with pytest.raises(RuntimeError, match="failed"):
        lib.require()
