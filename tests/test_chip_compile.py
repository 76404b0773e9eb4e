"""Compile every Pallas kernel of the main path for a described TPU v5e.

No chip is needed: the TPU compiler in the installed libtpu compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what
Mosaic would refuse on the chip (unaligned slices, VMEM or SMEM over the
limit, unsupported casts and reductions) — none of which interpret-mode
tests can see.  Shapes are the s22 ef16 Graph500 R-MAT layout's (net
2^28, vperm 2^23, vr 4194528, the real in-degree classes).
"""

from __future__ import annotations

import ast
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from bfs_tpu.graph import benes
from bfs_tpu.graph.relay import COMPACT_MIN_D, ClassSlice, StageSpec
from bfs_tpu.ops import relay_pallas as RP

#: The s22 ef16 relay layout (seed 1): net and vperm sizes, padded vertex
#: space and in-degree classes (width, va, vb, sa, sb, real, vertex_major,
#: real_width) — from graph/relay.seg_classes on the generated graph.
S22_NET, S22_VPERM, S22_VR = 1 << 28, 1 << 23, 4194528
S22_IN_CLASSES = (
    (1, 0, 2350208, 0, 2350208, 2350184, False, 1),
    (2, 2350208, 2643648, 2350208, 2937088, 293431, False, 2),
    (3, 2643648, 2836896, 2937088, 3516832, 193243, False, 3),
    (4, 2836896, 2971200, 3516832, 4054048, 134294, False, 4),
    (6, 2971200, 3133760, 4054048, 5029408, 162557, False, 6),
    (8, 3133760, 3258272, 5029408, 6025504, 124497, False, 8),
    (12, 3258272, 3489472, 6025504, 8799904, 231172, False, 12),
    (16, 3489472, 3580896, 8799904, 10262688, 91414, False, 16),
    (24, 3580896, 3624704, 10262688, 11314080, 43802, False, 24),
    (32, 3624704, 3776224, 11314080, 16162720, 151511, False, 32),
    (48, 3776224, 3912992, 16162720, 22727584, 136764, False, 48),
    (64, 3912992, 3913856, 22727584, 22782880, 847, False, 64),
    (96, 3913856, 3974944, 22782880, 28647328, 61079, False, 96),
    (128, 3974944, 4083840, 28647328, 42586016, 108883, False, 128),
    (192, 4083840, 4084416, 42586016, 42696608, 570, False, 192),
    (384, 4084416, 4159008, 42696608, 71339936, 74586, False, 384),
    (1024, 4159008, 4177952, 71339936, 90738592, 18915, False, 1024),
    (1536, 4177952, 4185376, 90738592, 102141856, 7419, False, 1536),
    (4096, 4185376, 4192544, 102141856, 131501984, 7154, False, 4096),
    (256, 4192544, 4192559, 131501984, 131505824, 15, True, 256),
    (512, 4192559, 4192571, 131505824, 131511968, 12, True, 512),
    (3072, 4192571, 4192732, 131511968, 132006560, 161, True, 3072),
    (12288, 4192732, 4194272, 132006560, 150930080, 1540, True, 12288),
    (32768, 4194272, 4194503, 150930080, 158499488, 231, True, 32768),
    (131072, 4194503, 4194525, 158499488, 161383072, 22, True, 131072),
    (393216, 4194525, 4194526, 161383072, 161776288, 1, True, 393216),
)
#: The compiler option every relay program is compiled with.
OPTIONS = {"xla_tpu_scoped_vmem_limit_kib": "98304"}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / not describable here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, kernel: str):
    """Compile for the described chip; raises what Mosaic/XLA refuse.  The
    compiled module must hold the kernel as a custom call under its
    ``name=``, the name the device trace shows."""
    compiled = jax.jit(fn).lower(*args).compile(compiler_options=OPTIONS)
    assert re.search(rf"%{kernel}(\.\d+)? = .*custom-call\(",
                     compiled.as_text()), kernel
    return compiled


def _table(n: int) -> tuple[StageSpec, ...]:
    """The stored Beneš stage table of an n-element network (full nonzero
    range — the kernels' shapes do not depend on the route)."""
    out, off = [], 0
    for s in range(benes.num_stages(n)):
        d = benes.stage_distance(n, s)
        compact = d >= COMPACT_MIN_D
        nw = n // 64 if compact else n // 32
        out.append(StageSpec(d=d, offset=off, nwords=nw, compact=compact,
                             lo=0, hi=nw))
        off += nw
    return tuple(out)


def _pass_rows(n: int, mode: str, tr: int, specs) -> int:
    """Rows of a pass's [rows, 128] mask operand (prepare_pass_masks)."""
    if mode == "local_tm":
        r = n // 32 // RP.LANES
        return (r // tr) * sum(RP._stage_rows(st, tr) for st in specs)
    return sum(st.nwords for st in specs) // RP.LANES


def _benes_passes(n: int):
    return [
        (p, _pass_rows(n, *p[:2], p[3]))
        for p in RP.pass_static(_table(n), n)
    ]


@pytest.fixture
def layout_knobs(monkeypatch):
    """Pin the pass-layout knobs (another test module may export
    ``BFS_TPU_LANE_COMPACT=1`` into this worker's environment)."""
    monkeypatch.setenv("BFS_TPU_LANE_COMPACT", "0")
    monkeypatch.setattr(RP, "TILE_MAJOR", True)
    return monkeypatch


@pytest.mark.parametrize("mode", ["outer", "local_tm"])
def test_benes_pass_compiles(one_chip, layout_knobs, mode):
    """The fused Beneš passes of the s22 net (the vperm net shares the
    local pass's tile): the outer pass and the tile-major local pass."""
    n = S22_NET
    (p, rows), = [x for x in _benes_passes(n) if x[0][0] == mode][:1]
    _compile(
        lambda w, a: RP.apply_benes_fused(w, (a,), (p,), n),
        _shape(one_chip, (n // 32,)), _shape(one_chip, (rows, RP.LANES)),
        kernel={"outer": "relay_net_outer", "local_tm": "relay_net_local"}[mode],
    )


def test_benes_row_major_local_pass_compiles(one_chip, layout_knobs):
    """The per-stage DMA local pass (``BFS_TPU_TM=0``)."""
    layout_knobs.setattr(RP, "TILE_MAJOR", False)
    n = S22_VPERM
    (p, rows), = [x for x in _benes_passes(n) if x[0][0] == "local"][:1]
    _compile(
        lambda w, a: RP.apply_benes_fused(w, (a,), (p,), n),
        _shape(one_chip, (n // 32,)), _shape(one_chip, (rows, RP.LANES)),
        kernel="relay_net_local",
    )


def _elem_passes(n: int):
    """The element-major passes of an n-element net, one per distinct
    block shape (mode, m)."""
    seen, out = set(), []
    for p in RP.elem_pass_static(_table(n), n):
        if (p[0], p[4]) not in seen:
            seen.add((p[0], p[4]))
            out.append((p, sum(st.nwords for st in p[3]) // RP.LANES))
    return out


@pytest.mark.parametrize("mode", ["local", "outer"])
def test_benes_elem_pass_compiles(one_chip, mode):
    """The element-major passes (32 trees per element) of the s22 net:
    the local pass and each outer window shape."""
    n = S22_NET
    passes = [x for x in _elem_passes(n) if x[0][0] == mode]
    assert passes
    for p, rows in passes:
        _compile(
            lambda x, a, p=p: RP.apply_benes_elem_fused(x, (a,), (p,), n),
            _shape(one_chip, (1, n)), _shape(one_chip, (rows, RP.LANES)),
            kernel="relay_net_elem",
        )


def test_rowmin_compiles(one_chip):
    """The masked row-min tournament over every eligible s22 class."""
    classes = tuple(ClassSlice(*c) for c in S22_IN_CLASSES)
    assert any(RP.rowmin_class_ok(cs) for cs in classes)
    words = _shape(one_chip, (S22_NET // 32,))
    _compile(
        lambda l1, v: RP.rowmin_ranks_pallas(
            l1, v, classes, S22_VR, interpret=False
        ),
        words, words, kernel="relay_rowmin",
    )


def test_state_update_compiles(one_chip):
    """The packed lexicographic-min state update with the frontier repack."""
    from bfs_tpu.ops.relay import PackedRelayState

    st = PackedRelayState(
        _shape(one_chip, (S22_VR,)), _shape(one_chip, (S22_VR // 32,)),
        _shape(one_chip, (), jnp.int32), _shape(one_chip, (), jnp.bool_),
    )
    _compile(
        lambda s, c: RP.apply_relay_candidates_packed_pallas(
            s, c, interpret=False
        ),
        st, _shape(one_chip, (S22_VR,)), kernel="relay_apply_packed",
    )


def test_mxu_expansion_compiles(one_chip):
    """The MXU expansion at s22 rows with 2^20 tiles: tile indices beyond
    what SMEM holds at once, so they stream in chunks."""
    from bfs_tpu.ops import relay_mxu as MX

    rows = cols = S22_VR
    rtp = -(-rows // MX.TILE) * MX.TILE
    vtp = -(-cols // MX.SB_VERTS) * MX.SB_VERTS
    ntp = 1 << 20
    ops = (
        _shape(one_chip, (ntp // 2, 2 * MX.TILE_WORDS, MX.TILE)),
        _shape(one_chip, (ntp,), jnp.int32),
        _shape(one_chip, (ntp,), jnp.int32),
        _shape(one_chip, (vtp // MX.SB_VERTS + 1,), jnp.int32),
        _shape(one_chip, (-(-(rtp // MX.TILE + 1) // 8), 8, MX.TILE)),
        _shape(one_chip, (ntp // MX._IDX_CHUNK, 2, MX._IDX_CHUNK),
               jnp.int32),
    )
    _compile(
        lambda fw, o: MX.expand_frontier_mxu(
            fw, o, rows=rows, cols=cols, rtp=rtp, vtp=vtp, interpret=False
        ),
        _shape(one_chip, (S22_VR // 32,)), ops, kernel="mxu_expand",
    )


#: The name each Pallas call site gives its kernel: the name under which
#: the device trace shows it, and which the benchmark's readers select.
KERNEL_NAMES = {
    "bfs_tpu/ops/relay_pallas.py::_run_local_tile_major": "'relay_net_local'",
    "bfs_tpu/ops/relay_pallas.py::_run_pass": "f'relay_net_{mode}'",
    "bfs_tpu/ops/relay_pallas.py::_run_elem_pass": "'relay_net_elem'",
    "bfs_tpu/ops/relay_pallas.py::_class_tournament_call": "'relay_rowmin'",
    "bfs_tpu/ops/relay_pallas.py::apply_relay_candidates_packed_pallas":
        "'relay_apply_packed'",
    "bfs_tpu/ops/relay_mxu.py::expand_frontier_mxu": "'mxu_expand'",
}


def test_every_pallas_call_names_its_kernel():
    """Every ``pallas_call`` in ``bfs_tpu/`` passes ``name=``; the names
    are the table's, so a new call site has to be named here too."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    found = {}
    for rel in sorted({site.split("::")[0] for site in KERNEL_NAMES}):
        tree = ast.parse(open(os.path.join(repo, rel), encoding="utf-8").read())
        for fn in tree.body:
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) and getattr(
                        node.func, "attr", None) == "pallas_call":
                    name = [k.value for k in node.keywords if k.arg == "name"]
                    found[f"{rel}::{fn.name}"] = (
                        ast.unparse(name[0]) if name else None)
    assert found == KERNEL_NAMES
    from bfs_tpu.analysis.pallas import discover_pallas_sites

    assert discover_pallas_sites(repo) == set(KERNEL_NAMES)
