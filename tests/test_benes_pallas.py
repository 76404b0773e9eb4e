"""v4 fused-pass Pallas kernels vs the XLA per-stage reference.

Runs in Pallas interpret mode on the CPU test platform: same kernel code
path as the TPU (minus Mosaic lowering), bit-exact against apply_benes_std.
The real-TPU compiled path is additionally exercised by the bench's check()
invariants (bfs_tpu/bench.py) on every benchmark run.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Exercise the lane-compacted pass-B path (opt-in on real runs — slower in
# fast-DMA windows, kept for DMA-starved ones; see relay_pallas).
os.environ["BFS_TPU_LANE_COMPACT"] = "1"

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bfs_tpu.graph import benes  # noqa: E402

if not benes.native_available():  # pragma: no cover
    pytest.skip("native benes router unavailable", allow_module_level=True)

import jax.numpy as jnp  # noqa: E402

from bfs_tpu.graph.relay import _compact_and_table  # noqa: E402
from bfs_tpu.ops.relay import apply_benes_std, pack_std, unpack_std  # noqa: E402
from bfs_tpu.ops.relay_pallas import (  # noqa: E402
    apply_benes_fused,
    pass_static,
    prepare_pass_masks,
)


@pytest.mark.parametrize("tile_rows", [16, 64])
def test_fused_passes_match_xla(tile_rows):
    """All three fused passes (outer prefix, local run, outer suffix) with
    compacted masks and tail-range skips route exactly perm."""
    rng = np.random.default_rng(5)
    n = 1 << 19  # r = 128 rows; tile_rows < r forces outer passes
    perm = rng.permutation(n).astype(np.int64)
    masks, table = _compact_and_table(benes.route_std(perm), n)
    ps = pass_static(table, n, tile_rows=tile_rows)
    arrays = [
        jnp.asarray(a)
        for a in prepare_pass_masks(masks, table, n, tile_rows=tile_rows)
    ]
    from bfs_tpu.ops.relay_pallas import _is_lane_compact

    # 3 passes; +1 array when the local pass lane-compacts any stage (the
    # lane64 side array is emitted right after the local array).
    local_specs = next(sp for m, _t, _tt, sp in ps if m == "local")
    n_lane = 1 if any(_is_lane_compact(st) for st in local_specs) else 0
    assert len(ps) == 3 and len(arrays) == 3 + n_lane
    assert n_lane == 1  # d=2^9..2^11 stages exist at n=2^19
    bits = rng.integers(0, 2, size=n).astype(np.uint8)
    x = pack_std(jnp.asarray(bits))
    want = np.asarray(
        unpack_std(apply_benes_std(x, jnp.asarray(masks), table, n), n)
    )
    got_x = apply_benes_fused(x, arrays, ps, n, interpret=True)
    got = np.asarray(unpack_std(got_x, n))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, bits[perm])


def test_fused_identity_tail_skips_are_correct():
    """A permutation with a large identity tail: with live <= n/2 the pad
    pairs are pure and route switch-free, so stages carry skippable nonzero
    ranges; the guarded DMA/compute path must still route exactly."""
    rng = np.random.default_rng(6)
    n = 1 << 19
    live = n * 3 // 8
    perm = np.arange(n, dtype=np.int64)
    perm[:live] = rng.permutation(live)
    masks, table = _compact_and_table(benes.route_std(perm), n)
    # the tail must actually produce skippable ranges
    assert any(st.hi < st.nwords for st in table)
    ps = pass_static(table, n, tile_rows=16)
    arrays = [
        jnp.asarray(a) for a in prepare_pass_masks(masks, table, n, tile_rows=16)
    ]
    bits = rng.integers(0, 2, size=n).astype(np.uint8)
    x = pack_std(jnp.asarray(bits))
    x = apply_benes_fused(x, arrays, ps, n, interpret=True)
    np.testing.assert_array_equal(np.asarray(unpack_std(x, n)), bits[perm])


def test_apply_benes_fused_end_to_end():
    rng = np.random.default_rng(7)
    n = 1 << 19
    perm = rng.permutation(n).astype(np.int64)
    masks, table = _compact_and_table(benes.route_std(perm), n)
    ps = pass_static(table, n, tile_rows=32)
    arrays = [
        jnp.asarray(a) for a in prepare_pass_masks(masks, table, n, tile_rows=32)
    ]
    bits = rng.integers(0, 2, size=n).astype(np.uint8)
    out = apply_benes_fused(
        pack_std(jnp.asarray(bits)), arrays, ps, n, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(unpack_std(out, n)), bits[perm])


@pytest.mark.parametrize(
    "outer_bits, modes",
    [
        (8, ["outer", "local", "outer"]),
        # one block bit per outer pass: every outer pass is strided over
        # the block axis (hi > 1 or tr > tile rows)
        (1, ["outer", "outer", "local", "outer", "outer"]),
    ],
)
def test_elem_fused_passes_match_reference(outer_bits, modes):
    """Element-major fused passes (uint32 per element, vertically-packed
    masks) route exactly perm on whole uint32 payloads, both groups."""
    import jax.numpy as jnp

    from bfs_tpu.ops.relay_elem import apply_benes_elem
    from bfs_tpu.ops.relay_pallas import (
        _run_elem_pass,
        elem_pass_static,
        prepare_elem_pass_masks,
    )

    rng = np.random.default_rng(9)
    n = 1 << 16
    perm = rng.permutation(n).astype(np.int64)
    masks, table = _compact_and_table(benes.route_std(perm), n)
    ps = elem_pass_static(table, n, tile_rows=128, outer_tt=32,
                          outer_bits=outer_bits)
    arrays = [
        jnp.asarray(a)
        for a in prepare_elem_pass_masks(masks, table, n, tile_rows=128,
                                         outer_tt=32, outer_bits=outer_bits)
    ]
    assert [p[0] for p in ps] == modes
    x = rng.integers(0, 2**32, (2, n), dtype=np.uint32)
    want = np.asarray(
        apply_benes_elem(jnp.asarray(x), jnp.asarray(masks), table, n)
    )
    np.testing.assert_array_equal(want, x[:, perm])
    got = jnp.asarray(x)
    for (mode, tr, tt, specs, m), arr in zip(ps, arrays):
        got = _run_elem_pass(got, arr, mode, tr, tt, specs, m, n, True)
    np.testing.assert_array_equal(np.asarray(got), want)
