"""IR-grade static analysis: lower the hot fused programs to jaxprs and
check semantic invariants per compiled artifact.

The PR 4 AST linter polices what the SOURCE says; this pass polices what
XLA actually lowers.  Every declared hot program — the push/pull/relay/
direction/multisource fused ``while_loop`` runners, the serve batch
executables, the per-superstep step bodies and the shard_map mesh
programs — is built at a tiny deterministic scale, traced to a jaxpr,
and walked for invariants the AST cannot see:

* **IR001 donation** — V-sized carries (packed state words, frontier
  words) that the program consumes but does not donate: the dead input
  and the live output coexist, doubling the carry's HBM bytes.  The
  finding reports the doubled bytes.
* **IR002 host round-trips** — callback/device_put-shaped eqns inside a
  fused loop body.  One mid-loop callback turns a single compiled
  superstep loop into a per-superstep host sync.
* **IR003 dtype drift** — packed ``level:6|parent:26`` uint32 words
  widened to f32/f64/i64 inside a loop body, or telemetry accumulators
  drifting to 64-bit (an accidental x64 promotion doubles their bytes
  and the exchange that carries them).
* **IR004 HBM budget proof** — a static footprint estimate (operands +
  outputs + a double-buffered temp watermark from eqn shapes) checked
  against the program's byte budget.  The estimate is a LOWER bound: a
  config that fails it cannot fit, full stop.
* **IR005/IR006 collective correctness** — mesh-axis use, required
  exchange collectives and payload dtype/width for the shard_map
  programs (:mod:`bfs_tpu.analysis.collectives`).

Unlike the AST half this module imports jax — it is loaded only by the
``--ir`` CLI path and the IR tests, never by ``bfs_tpu.analysis`` itself.
Tracing every program costs seconds, so results are cached
content-addressed (like JAX's persistent compile cache):
the key hashes every ``bfs_tpu`` source file plus the jax version,
backend, device count and the env knobs that select program flavors.
Tier-1 reruns are a cache hit unless the package actually changed.

Baseline: IR findings share ``baseline.txt``.  Their fingerprints hash
``(rule, path, "ir:<program>:<detail>")`` — stable under any source-line
drift, invalidated exactly when the program or the violation changes.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
from dataclasses import dataclass, field

from .. import knobs
from .core import Finding

#: Bump to invalidate every cached IR result (rule semantics changed).
IR_VERSION = 1

#: Env knobs that change which program flavors the registry builds —
#: DERIVED from the registry (``affects`` contains ``ir``); KNB002
#: proves membership against bfs_tpu/knobs.py both ways instead of a
#: hand-maintained list (the PR 15 stale-cache bug class).
_FLAVOR_ENV = knobs.flavor_env("ir")

#: Primitives whose presence in a loop body is a host round-trip (IR002).
_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
})
_TRANSFER_PRIMS = frozenset({"device_put"})

_LOOP_PRIMS = frozenset({"while", "scan"})

_WIDE = ("int64", "uint64", "float64")


class SkipProgram(Exception):
    """A spec builder may raise this (e.g. too few devices for a mesh
    program) — recorded as skipped, never as a finding."""


@dataclass
class Program:
    """One built hot-program artifact plus its declared invariants.

    ``fn(*args, **static_kwargs)`` must be traceable by
    ``jax.make_jaxpr`` — typically the repo's own jit-wrapped program
    object, so donation/sharding metadata is exactly what ships.
    """

    name: str
    path: str  # repo-relative source anchor for findings
    fn: object
    args: tuple
    static_kwargs: dict = field(default_factory=dict)
    #: arrays with at least this many elements are "V-sized"
    v_elements: int = 0
    packed: bool = False
    #: arg index -> label for carries the program consumes (IR001)
    donate: dict = field(default_factory=dict)
    budget_bytes: int | None = None
    #: mesh axes the program is allowed to exchange over (None = no check)
    mesh_axes: frozenset | None = None
    #: axes that MUST see at least one collective
    required_axes: frozenset = frozenset()
    #: per-flat-output axis sets a shard_map must produce (None = no check)
    expected_out_names: tuple | None = None
    #: allowed dtypes for V-scale collective payloads (IR006)
    exchange_dtypes: tuple = ("uint32", "int32", "bool")
    #: collective payloads under this many bytes are control scalars
    exchange_floor: int = 1024
    #: expected multiset of replica-group SIZES of loop-body PAYLOAD
    #: collectives (payload = any non-scalar result, >1 element — the
    #: control scalars like `changed`/masses psums are excluded by shape,
    #: not by a byte floor).  None = no check.  The 2D grid programs
    #: declare exactly one collective per mesh axis per superstep:
    #: ``(c, r)`` — the column broadcast at group size c, the row
    #: min-reduce at group size r (HLO004).
    loop_payload_groups: tuple | None = None


@dataclass(frozen=True)
class WalkCtx:
    in_loop: bool = False
    mesh_axes: frozenset | None = None


def walk_eqns(jaxpr, ctx: WalkCtx = WalkCtx()):
    """Yield ``(eqn, ctx)`` over a jaxpr and every sub-jaxpr (while/cond
    bodies, pjit calls, shard_map regions, scans, pallas kernels).  The
    context records whether the eqn sits inside a device loop body and
    which mesh axes the nearest enclosing shard_map binds."""
    for eqn in jaxpr.eqns:
        yield eqn, ctx
        name = eqn.primitive.name
        sub_ctx = WalkCtx(
            in_loop=ctx.in_loop or name in _LOOP_PRIMS,
            mesh_axes=(
                frozenset(str(a) for a in eqn.params["mesh"].axis_names)
                if name == "shard_map"
                else ctx.mesh_axes
            ),
        )
        for sub in _eqn_jaxprs(eqn):
            yield from walk_eqns(sub, sub_ctx)


def _eqn_jaxprs(eqn):
    found = []
    for v in eqn.params.values():
        found.extend(_jaxprs_in(v))
    return found


def _jaxprs_in(v):
    if hasattr(v, "eqns"):  # core.Jaxpr or ClosedJaxpr
        return [v]
    if isinstance(v, (tuple, list)):
        out = []
        for x in v:
            out.extend(_jaxprs_in(x))
        return out
    return []


def _aval_bytes(aval) -> int:
    dtype = getattr(aval, "dtype", None)
    size = getattr(aval, "size", None)
    if dtype is None or size is None:
        return 0
    return int(size) * dtype.itemsize


# --------------------------------------------------------------------------
# Per-rule checks.
# --------------------------------------------------------------------------

def _check_donation(prog: Program, closed, make_finding):
    """IR001: declared carries must reach their pjit donated."""
    if not prog.donate:
        return []
    import jax

    ranges, start = [], 0
    for a in prog.args:
        n = len(jax.tree_util.tree_leaves(a))
        ranges.append((start, start + n))
        start += n
    invars = closed.jaxpr.invars
    donated = [False] * len(invars)
    var_index = {id(v): i for i, v in enumerate(invars)}
    for eqn, _ctx in walk_eqns(closed.jaxpr):
        if eqn.primitive.name != "jit":
            continue
        flags = eqn.params.get("donated_invars") or ()
        for j, v in enumerate(eqn.invars):
            i = var_index.get(id(v))
            if i is not None and j < len(flags) and flags[j]:
                donated[i] = True
    findings = []
    for argidx, label in sorted(prog.donate.items()):
        lo, _hi = ranges[argidx]
        leaves = jax.tree_util.tree_leaves(prog.args[argidx])
        missing = 0
        for off, leaf in enumerate(leaves):
            size = int(getattr(leaf, "size", 0))
            if size >= prog.v_elements and not donated[lo + off]:
                missing += size * leaf.dtype.itemsize
        if missing:
            findings.append(make_finding(
                "IR001", f"donate:{label}",
                f"carry '{label}' is consumed but not donated: "
                f"{missing} dead input bytes stay live next to the "
                f"output — peak HBM for the call is doubled "
                f"(+{missing} bytes); donate argnum {argidx}",
            ))
    return findings


def _check_loop_body(prog: Program, walked, make_finding):
    """IR002 (host round-trips) + IR003 (dtype drift) inside loop bodies."""
    findings = []
    for eqn, ctx in walked:
        name = eqn.primitive.name
        if ctx.in_loop and (
            name in _CALLBACK_PRIMS or name in _TRANSFER_PRIMS
        ):
            findings.append(make_finding(
                "IR002", f"loop:{name}",
                f"'{name}' eqn inside the fused loop body — every "
                "superstep would round-trip through the host",
            ))
        elif name == "convert_element_type":
            in_aval = eqn.invars[0].aval
            new = str(eqn.params.get("new_dtype"))
            src = str(getattr(in_aval, "dtype", ""))
            size = int(getattr(in_aval, "size", 0))
            if not ctx.in_loop or size < prog.v_elements:
                continue
            if src == "uint32" and (new in _WIDE or new == "float32"):
                findings.append(make_finding(
                    "IR003", f"widen:{src}->{new}",
                    f"packed uint32 words ({size} elements) converted to "
                    f"{new} inside the loop body — the level|parent "
                    "packing does not survive a float/64-bit detour",
                ))
            elif src == "int32" and new in _WIDE:
                findings.append(make_finding(
                    "IR003", f"widen:{src}->{new}",
                    f"int32 loop state ({size} elements) widened to "
                    f"{new} inside the loop body (x64 drift doubles its "
                    "bytes)",
                ))
        elif name == "while":
            body = eqn.params["body_jaxpr"]
            for v in body.jaxpr.outvars:
                aval = getattr(v, "aval", None)
                dt = str(getattr(aval, "dtype", ""))
                size = int(getattr(aval, "size", 0))
                if dt in _WIDE:
                    findings.append(make_finding(
                        "IR003", f"carry:{dt}",
                        f"loop carry of dtype {dt} ({size} elements) — "
                        "64-bit state in the fused loop is always drift",
                    ))
                elif (
                    prog.packed and dt == "float32"
                    and size >= prog.v_elements
                ):
                    findings.append(make_finding(
                        "IR003", "carry:float32",
                        f"packed program carries a V-sized float32 array "
                        f"({size} elements) through the loop — the packed "
                        "state contract is uint32 words",
                    ))
    return findings


def _check_budget(prog: Program, closed, walked, make_finding):
    """IR004: operands + outputs + double-buffered temp watermark must
    fit the declared byte budget."""
    if not prog.budget_bytes:
        return []
    operands = sum(_aval_bytes(v.aval) for v in closed.jaxpr.invars)
    consts = sum(
        int(getattr(c, "nbytes", 0)) for c in getattr(closed, "consts", ())
    )
    outputs = sum(
        _aval_bytes(getattr(v, "aval", None)) for v in closed.jaxpr.outvars
    )
    temp = 0
    for eqn, _ctx in walked:
        temp = max(
            temp, sum(_aval_bytes(getattr(v, "aval", None))
                      for v in eqn.outvars)
        )
    estimate = operands + consts + outputs + 2 * temp
    if estimate > prog.budget_bytes:
        return [make_finding(
            "IR004", "budget",
            f"static footprint estimate {estimate} bytes (operands "
            f"{operands + consts} + outputs {outputs} + 2x temp watermark "
            f"{temp}) exceeds the declared budget {prog.budget_bytes} "
            "bytes — this config cannot fit",
        )]
    return []


def analyze_program(prog: Program) -> list[Finding]:
    """All IR findings for one built program (deduped, sorted)."""
    import jax

    from .collectives import check_collectives

    def make_finding(rule: str, detail: str, message: str) -> Finding:
        return Finding(
            rule=rule, path=prog.path, line=0, col=0,
            message=f"[{prog.name}] {message}",
            snippet=f"ir:{prog.name}:{detail}",
        )

    try:
        closed = jax.make_jaxpr(
            lambda *a: prog.fn(*a, **prog.static_kwargs)
        )(*prog.args)
    except SkipProgram:
        raise
    except Exception as exc:
        return [make_finding(
            "IR000", "build",
            f"could not lower to a jaxpr: {type(exc).__name__}: {exc}",
        )]
    walked = list(walk_eqns(closed.jaxpr))
    findings = []
    findings += _check_donation(prog, closed, make_finding)
    findings += _check_loop_body(prog, walked, make_finding)
    findings += _check_budget(prog, closed, walked, make_finding)
    findings += check_collectives(prog, walked, make_finding)
    seen, out = set(), []
    for f in findings:
        key = (f.rule, f.snippet)
        if key not in seen:
            seen.add(key)
            out.append(f)
    out.sort(key=lambda f: (f.path, f.rule, f.snippet))
    return out


# --------------------------------------------------------------------------
# The hot-program registry: every declared fused program, built tiny.
# --------------------------------------------------------------------------

def _hbm_envelope() -> int:
    """Per-chip HBM budget the IR004 proof checks against.
    ``BFS_TPU_IR_HBM_GB`` overrides (e.g. a bench-scale run proving a
    real config); the default is the v5e envelope."""
    return int(knobs.get("BFS_TPU_IR_HBM_GB") * (1 << 30))


_BUILD_CACHE: dict = {}


def _memo(key, build):
    """Memoize expensive spec inputs (graphs, engines, meshes) within a
    process.  The key carries the flavor env so two analyze_ir calls
    under different knobs (tests monkeypatching BFS_TPU_PACKED etc.)
    never share an engine built for the other flavor — the result cache
    keys on the same env, and the two must agree."""
    key = (key, tuple(os.environ.get(e, "") for e in _FLAVOR_ENV))
    if key not in _BUILD_CACHE:
        _BUILD_CACHE[key] = build()
    return _BUILD_CACHE[key]


def _tiny_graph():
    def build():
        from ..graph.generators import rmat_graph

        return rmat_graph(6, 4, seed=3)

    return _memo("graph", build)


def _relay_engine():
    def build():
        from ..models.bfs import RelayEngine

        return RelayEngine(_tiny_graph())

    return _memo("relay_engine", build)


def _spec_push_fused():
    import jax.numpy as jnp

    from ..graph.csr import build_device_graph
    from ..models.bfs import _bfs_fused

    dg = _memo("dg", lambda: build_device_graph(_tiny_graph()))
    v = dg.num_vertices
    return Program(
        name="bfs.push_fused", path="bfs_tpu/models/bfs.py",
        fn=_bfs_fused,
        args=(jnp.asarray(dg.src), jnp.asarray(dg.dst), jnp.int32(0)),
        static_kwargs=dict(
            num_vertices=v, max_levels=v, packed=True, telemetry=True
        ),
        v_elements=v, packed=True, budget_bytes=_hbm_envelope(),
    )


def _spec_pull_fused():
    import jax.numpy as jnp

    from ..graph.ell import build_pull_graph, device_ell
    from ..models.bfs import _bfs_pull_fused

    pg = _memo("pg", lambda: build_pull_graph(_tiny_graph()))
    ell0, folds = _memo("ell", lambda: device_ell(pg))
    return Program(
        name="bfs.pull_fused", path="bfs_tpu/models/bfs.py",
        fn=_bfs_pull_fused,
        args=(ell0, folds, jnp.int32(0)),
        static_kwargs=dict(
            num_vertices=pg.num_vertices, max_levels=pg.num_vertices,
            packed=True, telemetry=True,
        ),
        v_elements=pg.num_vertices, packed=True,
        budget_bytes=_hbm_envelope(),
    )


def _spec_serve_batch(engine: str):
    """The serve batch executables (serve/executor.build_batch_runner
    lowers exactly these multisource programs at power-of-two buckets)."""
    import jax.numpy as jnp

    if engine == "pull":
        from ..graph.ell import build_pull_graph, device_ell
        from ..models.multisource import _bfs_multi_pull_fused

        pg = _memo("pg", lambda: build_pull_graph(_tiny_graph()))
        ell0, folds = _memo("ell", lambda: device_ell(pg))
        v = pg.num_vertices
        args = (ell0, folds, jnp.zeros((4,), jnp.int32))
        fn = _bfs_multi_pull_fused
    else:
        from ..graph.csr import build_device_graph
        from ..models.multisource import _bfs_multi_fused

        dg = _memo("dg", lambda: build_device_graph(_tiny_graph()))
        v = dg.num_vertices
        args = (
            jnp.asarray(dg.src), jnp.asarray(dg.dst),
            jnp.zeros((4,), jnp.int32),
        )
        fn = _bfs_multi_fused
    return Program(
        name=f"serve.batch_{engine}", path="bfs_tpu/serve/executor.py",
        fn=fn, args=args,
        static_kwargs=dict(
            num_vertices=v, max_levels=v, packed=True, telemetry=False
        ),
        v_elements=v, packed=True, budget_bytes=_hbm_envelope(),
    )


def _spec_label_lookup():
    """The serve label tier's point-query program (serve/labels.py):
    one batched gather+min over the uint16[K, V] landmark rows — no
    V-sized carry, no donation (IR001 trivially holds), and its whole
    point is being orders of magnitude smaller than a traversal."""
    import jax.numpy as jnp

    from ..serve.labels import _label_bounds, build_label_index

    idx = _memo("labels", lambda: build_label_index(_tiny_graph(), 3))
    return Program(
        name="serve.label_lookup", path="bfs_tpu/serve/labels.py",
        fn=_label_bounds,
        args=(
            jnp.asarray(idx.dist),
            jnp.zeros((4,), jnp.int32),
            jnp.ones((4,), jnp.int32),
        ),
        v_elements=idx.num_vertices,
        budget_bytes=_hbm_envelope(),
    )


def _spec_direction_fused():
    import jax.numpy as jnp

    from ..models.direction import _bfs_direction_fused, _direction_operands

    dg, ell0, folds, outdeg = _memo(
        "dir_ops", lambda: _direction_operands(_tiny_graph())
    )
    v = dg.num_vertices
    return Program(
        name="direction.fused_auto", path="bfs_tpu/models/direction.py",
        fn=_bfs_direction_fused,
        args=(
            jnp.asarray(dg.src), jnp.asarray(dg.dst), ell0, folds, outdeg,
            jnp.zeros((4,), jnp.int32), jnp.float32(14.0), jnp.float32(24.0),
        ),
        static_kwargs=dict(
            num_vertices=v, max_levels=v, packed=True, mode="auto"
        ),
        v_elements=v, packed=True, budget_bytes=_hbm_envelope(),
    )


def _spec_relay_fused():
    import jax.numpy as jnp

    from ..models.bfs import _relay_fused_program

    eng = _relay_engine()
    fused = _relay_fused_program(
        eng._static, eng.sparse_hybrid, eng._use_pallas(), eng.packed,
        False, eng.direction.key(), eng._phase_sel(),
        eng.relay_graph.num_vertices,
    )
    return Program(
        name="relay.fused", path="bfs_tpu/models/bfs.py",
        fn=fused,
        args=(
            jnp.int32(0), *eng._tensors,
            *eng._sparse_tensors_for(eng.packed),
        ),
        static_kwargs=dict(max_levels=16),
        v_elements=eng.relay_graph.vr, packed=eng.packed,
        budget_bytes=_hbm_envelope(),
    )


def _relay_engine_mxu():
    def build():
        from ..models.bfs import RelayEngine

        return RelayEngine(_tiny_graph(), expansion="mxu")

    return _memo("relay_engine_mxu", build)


def _spec_relay_fused_mxu():
    """The MXU expansion arm's fused program (ISSUE 15): the same loop
    scaffolding as relay.fused with the tiled masked-matmul dense body
    and key-flavor sparse adjacency — donation/transfer/dtype/footprint
    rules must hold for the new arm exactly as for the gather one."""
    import jax.numpy as jnp

    from ..models.bfs import _relay_fused_program

    eng = _relay_engine_mxu()
    fused = _relay_fused_program(
        eng._static, eng.sparse_hybrid, eng._use_pallas(), eng.packed,
        False, eng.direction.key(), eng._phase_sel(),
        eng.relay_graph.num_vertices, eng._expansion_key(),
    )
    return Program(
        name="relay.fused_mxu", path="bfs_tpu/models/bfs.py",
        fn=fused,
        args=(
            jnp.int32(0), *eng._mxu_mask_args(),
            *eng._sparse_tensors_for(eng.packed),
        ),
        static_kwargs=dict(max_levels=16),
        v_elements=eng.relay_graph.vr, packed=eng.packed,
        budget_bytes=_hbm_envelope(),
    )


def _spec_relay_segment_mxu():
    """The mxu arm's checkpointable segment twin (ISSUE 15): carry
    donated per segment like relay.segment."""
    import jax.numpy as jnp

    from ..models.bfs import _relay_segment_program

    eng = _relay_engine_mxu()
    prog = _relay_segment_program(
        eng._static, eng.sparse_hybrid, eng._use_pallas(), eng.packed,
        True, eng.direction.key(), eng._phase_sel(),
        eng.relay_graph.num_vertices, eng._expansion_key(),
    )
    carry = eng.segment_carry(0, telemetry=True)
    return Program(
        name="relay.segment_mxu", path="bfs_tpu/models/bfs.py",
        fn=prog,
        args=(
            carry, jnp.int32(8), *eng._mxu_mask_args(),
            *eng._sparse_tensors_for(eng.packed),
        ),
        static_kwargs=dict(max_levels=16),
        v_elements=eng.relay_graph.vr, packed=eng.packed,
        donate={0: "carry"}, budget_bytes=_hbm_envelope(),
    )


def _spec_sharded_relay_mxu():
    """The sharded mxu arm (ISSUE 15): per-shard tiles against the
    all-gathered global frontier — the exchange contract (IR005/IR006)
    must hold unchanged since the superstep tail is body-agnostic."""
    from ..parallel.sharded import make_mesh

    _need_devices(2)
    import jax.numpy as jnp

    from ..ops.packed import packed_rank_fits, resolve_packed
    from ..parallel.sharded import (
        _bfs_sharded_relay_fused,
        _own_word_table_dev,
        _prepare_relay,
        _resolve_sharded_expansion,
        _sharded_adj_dev,
        _sharded_relay_static,
        _sharded_tiles_dev,
    )

    mesh = _memo("mesh2", lambda: make_mesh(graph=2, batch=1))
    srg = _memo("srg2", lambda: _prepare_relay(_tiny_graph(), mesh))
    packed = resolve_packed(packed_rank_fits(srg.in_classes))
    exp_static, packed = _resolve_sharded_expansion("mxu", srg, packed)
    static = _sharded_relay_static(srg, 2, False, packed, exp_static)
    tiles_arg = _sharded_tiles_dev(srg)[0]
    dummy = jnp.zeros((2, 1), jnp.uint32)
    adj = _sharded_adj_dev(srg, packed, True)
    direction = ("auto", 14.0, 24.0, srg.num_vertices, srg.num_edges)
    return Program(
        name="sharded.relay_mxu", path="bfs_tpu/parallel/sharded.py",
        fn=_bfs_sharded_relay_fused,
        args=(
            tiles_arg, dummy, dummy, _own_word_table_dev(srg), *adj,
            jnp.asarray(srg.outdeg), jnp.int32(0),
        ),
        static_kwargs=dict(
            mesh=mesh, static=static, max_levels=16, telemetry=True,
            direction=direction, exchange=("auto", 8), sparse=True,
        ),
        v_elements=srg.num_vertices, packed=packed,
        budget_bytes=_hbm_envelope(),
        mesh_axes=frozenset({"graph", "batch"}),
        required_axes=frozenset({"graph"}),
    )


def _grid_spec_parts():
    """Shared inputs for the 2D grid specs: the 2x4 mesh over the
    virtual x8 platform, the 8-shard relay graph and its per-cell
    layout operands."""
    _need_devices(8)
    import jax.numpy as jnp

    from ..graph.grid_layout import grid_layout_for
    from ..ops.packed import packed_parent_fits, resolve_packed
    from ..parallel.grid import (
        _grid_dev_operands,
        _grid_static,
        _prepare_grid,
        make_grid_mesh,
    )
    from ..parallel.sharded import _own_word_table_dev

    mesh = _memo("grid_mesh24", lambda: make_grid_mesh(2, 4))
    srg = _memo("grid_srg8", lambda: _prepare_grid(_tiny_graph(), 8))
    packed = resolve_packed(packed_parent_fits(srg.num_vertices))
    layout = grid_layout_for(srg, 2, 4)
    operands = _grid_dev_operands(srg, 2, 4)
    own = _own_word_table_dev(srg)
    outdeg = jnp.asarray(srg.outdeg)
    static = _grid_static(layout, packed)
    return mesh, srg, packed, static, operands, own, outdeg


def _spec_grid_relay(flavor: str):
    """The 2D grid programs (ISSUE 17): candidate production local to
    the r x c cell, a row-axis min-reduce and a column-axis frontier
    broadcast — per-chip wire O(V/sqrt(n)).  ``bitmap`` (forced arm, no
    direction cond) carries the STRICT collective-count contract: the
    loop body must compile exactly one payload collective per mesh axis
    per superstep — group sizes (c, r) = (4, 2) — so a stray global
    all-gather (the 1D O(V) wire pattern) is an HLO004 finding, not a
    silent perf regression.  ``auto`` compiles both density arms under
    ``lax.cond`` (both branches sit in the loop computation, so the
    strict count would double-count) and is policed by the fingerprint
    row instead."""
    import jax.numpy as jnp

    from ..parallel.grid import _bfs_grid_fused

    mesh, srg, packed, static, operands, own, outdeg = _grid_spec_parts()
    if flavor == "auto":
        direction = ("auto", 14.0, 24.0, srg.num_vertices, srg.num_edges)
        exchange = ("auto", 8)
    else:
        direction = None
        exchange = ("bitmap", 8)
    return Program(
        name=f"grid.relay_{flavor}", path="bfs_tpu/parallel/grid.py",
        fn=_bfs_grid_fused,
        args=(*operands, own, outdeg, jnp.int32(int(srg.old2new[0]))),
        static_kwargs=dict(
            mesh=mesh, static=static, max_levels=16,
            telemetry=flavor == "auto", direction=direction,
            exchange=exchange,
        ),
        v_elements=srg.num_vertices, packed=packed,
        budget_bytes=_hbm_envelope(),
        mesh_axes=frozenset({"row", "col"}),
        required_axes=frozenset({"row", "col"}),
        loop_payload_groups=(4, 2) if flavor == "bitmap" else None,
    )


def _spec_grid_segment():
    """The bounded-segment grid program: per-cell checkpoint shards cut
    at the axis-exchange boundary — same per-axis collective contract
    as grid.relay_auto (the fused twin), policed by the fingerprint."""
    import jax.numpy as jnp

    from ..parallel.grid import _bfs_grid_segment, grid_segment_carry

    mesh, srg, packed, static, operands, own, outdeg = _grid_spec_parts()
    direction = ("auto", 14.0, 24.0, srg.num_vertices, srg.num_edges)
    carry = grid_segment_carry(
        srg, 2, 4, int(srg.old2new[0]), packed, True, True, outdeg
    )
    return Program(
        name="grid.segment", path="bfs_tpu/parallel/grid.py",
        fn=_bfs_grid_segment,
        args=(carry, jnp.int32(8), *operands, own, outdeg),
        static_kwargs=dict(
            mesh=mesh, static=static, max_levels=16, telemetry=True,
            direction=direction, exchange=("auto", 8),
        ),
        v_elements=srg.num_vertices, packed=packed,
        budget_bytes=_hbm_envelope(),
        mesh_axes=frozenset({"row", "col"}),
        required_axes=frozenset({"row", "col"}),
    )


def _spec_relay_multi_fused():
    import jax.numpy as jnp

    from ..models.bfs import _relay_multi_fused_program

    eng = _relay_engine()
    fused = _relay_multi_fused_program(
        eng._static, eng._use_pallas(), eng.packed, eng._phase_sel()
    )
    return Program(
        name="relay.multi_fused", path="bfs_tpu/models/bfs.py",
        fn=fused,
        args=(jnp.zeros((4,), jnp.int32), *eng._tensors),
        static_kwargs=dict(max_levels=16),
        v_elements=eng.relay_graph.vr, packed=eng.packed,
        budget_bytes=_hbm_envelope(),
    )


def _spec_relay_step(kind: str):
    """The AOT superstep bodies (RelayEngine._step_body): per-step
    programs whose state input is dead the moment they return — the
    canonical donation carries."""
    eng = _relay_engine()
    state = eng.init_hot_state(0)
    if kind == "sparse":
        args = (state, *eng._sparse_tensors_for(eng.packed)[:3])
    else:
        args = (state, *eng._tensors)
    return Program(
        name=f"relay.step_{kind}", path="bfs_tpu/models/bfs.py",
        fn=eng._step_fn(kind, eng.packed), args=args,
        v_elements=eng.relay_graph.vr, packed=eng.packed,
        donate={0: "state"},
    )


def _spec_relay_segment():
    """The bounded-segment relay program (ISSUE 14): the checkpointable
    twin of relay.fused — its carry is consumed per segment (callers
    reassign), so the whole carry dict is a declared donation (IR001)."""
    import jax.numpy as jnp

    from ..models.bfs import _relay_segment_program

    eng = _relay_engine()
    prog = _relay_segment_program(
        eng._static, eng.sparse_hybrid, eng._use_pallas(), eng.packed,
        True, eng.direction.key(), eng._phase_sel(),
        eng.relay_graph.num_vertices,
    )
    carry = eng.segment_carry(0, telemetry=True)
    return Program(
        name="relay.segment", path="bfs_tpu/models/bfs.py",
        fn=prog,
        args=(
            carry, jnp.int32(8), *eng._tensors,
            *eng._sparse_tensors_for(eng.packed),
        ),
        static_kwargs=dict(max_levels=16),
        v_elements=eng.relay_graph.vr, packed=eng.packed,
        donate={0: "carry"}, budget_bytes=_hbm_envelope(),
    )


def _spec_multi_segment(engine: str):
    """The bounded-segment batched multi-source programs (ISSUE 14) —
    what the serve checkpointing batch runner executes per segment."""
    import jax.numpy as jnp

    from ..models.multisource import multi_segment_init

    if engine == "pull":
        from ..graph.ell import build_pull_graph, device_ell
        from ..models.multisource import _bfs_multi_pull_segment

        pg = _memo("pg", lambda: build_pull_graph(_tiny_graph()))
        ell0, folds = _memo("ell", lambda: device_ell(pg))
        v = pg.num_vertices
        state = multi_segment_init(v, [0, 1, 2, 3], True)
        args = (ell0, folds, state, jnp.int32(8))
        fn = _bfs_multi_pull_segment
    else:
        from ..graph.csr import build_device_graph
        from ..models.multisource import _bfs_multi_segment

        dg = _memo("dg", lambda: build_device_graph(_tiny_graph()))
        v = dg.num_vertices
        state = multi_segment_init(v, [0, 1, 2, 3], True)
        args = (
            jnp.asarray(dg.src), jnp.asarray(dg.dst), state, jnp.int32(8)
        )
        fn = _bfs_multi_segment
    return Program(
        name=f"multisource.segment_{engine}",
        path="bfs_tpu/models/multisource.py",
        fn=fn, args=args,
        static_kwargs=dict(num_vertices=v, max_levels=v, packed=True),
        v_elements=v, packed=True, donate={2: "state"},
        budget_bytes=_hbm_envelope(),
    )


def _spec_sharded_relay_segment():
    """The bounded-segment sharded relay program (ISSUE 14): per-shard
    checkpoint shards cut at the exchange boundary — same collective
    contract as sharded.relay_push (IR005/IR006 police the exchange)."""
    from ..parallel.sharded import make_mesh

    _need_devices(2)
    import jax.numpy as jnp

    from ..ops.packed import packed_rank_fits, resolve_packed
    from ..parallel.sharded import (
        _bfs_sharded_relay_segment,
        _own_word_table_dev,
        _prepare_relay,
        _relay_valid_words,
        _sharded_adj_dev,
        _sharded_relay_mask_args,
        _sharded_relay_static,
        sharded_segment_carry,
    )

    mesh = _memo("mesh2", lambda: make_mesh(graph=2, batch=1))
    srg = _memo("srg2", lambda: _prepare_relay(_tiny_graph(), mesh))
    packed = resolve_packed(packed_rank_fits(srg.in_classes))
    vperm_arg, net_arg = _sharded_relay_mask_args(srg, False)
    static = _sharded_relay_static(srg, 2, False, packed)
    adj = _sharded_adj_dev(srg, packed)
    outdeg = jnp.asarray(srg.outdeg)
    direction = ("auto", 14.0, 24.0, srg.num_vertices, srg.num_edges)
    carry = sharded_segment_carry(
        srg, 2, int(srg.old2new[0]), packed, True, True, outdeg
    )
    return Program(
        name="sharded.relay_segment", path="bfs_tpu/parallel/sharded.py",
        fn=_bfs_sharded_relay_segment,
        args=(
            carry, jnp.int32(8), vperm_arg, net_arg,
            _relay_valid_words(srg), _own_word_table_dev(srg), *adj,
            outdeg,
        ),
        static_kwargs=dict(
            mesh=mesh, static=static, max_levels=16, telemetry=True,
            direction=direction, exchange=("auto", 8), sparse=True,
        ),
        v_elements=srg.num_vertices, packed=packed,
        budget_bytes=_hbm_envelope(),
        mesh_axes=frozenset({"graph", "batch"}),
        required_axes=frozenset({"graph"}),
    )


def _spec_superstep(engine: str):
    def build():
        from ..models.bfs import SuperstepRunner

        return SuperstepRunner(_tiny_graph(), engine=engine)

    runner = _memo(f"runner_{engine}", build)
    state = runner.init(0)
    return Program(
        name=f"superstep.{engine}_step", path="bfs_tpu/models/bfs.py",
        fn=runner._step, args=(state,),
        v_elements=runner.num_vertices, donate={0: "state"},
    )


def _spec_layout_device(prog_name: str):
    """The device layout-builder programs (graph/relay_device.py — the
    first-touch build path since ISSUE 10): classing histograms, relabel,
    slot sorts, permutation assembly, CSR, mask compaction and the
    pure-JAX Beneš route level.  Operands are captured from one real
    device build of the tiny graph (route=jax: no native dependency)."""
    from ..graph.relay_device import ir_operands

    ops = _memo("layout_device_ops", lambda: ir_operands(_tiny_graph()))
    fn, args, statics = ops[prog_name]
    return Program(
        name=prog_name, path="bfs_tpu/graph/relay_device.py",
        fn=fn, args=args, static_kwargs=statics,
        v_elements=_tiny_graph().num_vertices,
        budget_bytes=_hbm_envelope(),
    )


def _need_devices(n: int):
    import jax

    if len(jax.devices()) < n:
        raise SkipProgram(f"needs {n} devices, have {len(jax.devices())}")


def _spec_sharded_push():
    import jax.numpy as jnp

    from ..graph.csr import build_device_graph
    from ..parallel.sharded import make_mesh

    _need_devices(2)
    from ..parallel.sharded import _bfs_sharded_fused

    mesh = _memo("mesh2", lambda: make_mesh(graph=2, batch=1))
    dg = _memo(
        "dg2", lambda: build_device_graph(_tiny_graph(), num_shards=2)
    )
    v = dg.num_vertices
    return Program(
        name="sharded.push_fused", path="bfs_tpu/parallel/sharded.py",
        fn=_bfs_sharded_fused,
        args=(
            jnp.asarray(dg.src).reshape(2, -1),
            jnp.asarray(dg.dst).reshape(2, -1),
            jnp.int32(0),
        ),
        static_kwargs=dict(mesh=mesh, num_vertices=v, max_levels=16),
        v_elements=v, budget_bytes=_hbm_envelope(),
        mesh_axes=frozenset({"graph"}),
        required_axes=frozenset({"graph"}),
        # BfsState(dist, parent, frontier, level, changed) — replicated.
        expected_out_names=(frozenset(),) * 5,
    )


def _spec_sharded_pull():
    import jax.numpy as jnp

    from ..parallel.sharded import _prepare_pull, make_mesh

    _need_devices(2)
    from ..graph.ell import device_ell_sharded
    from ..parallel.sharded import _bfs_sharded_pull_fused

    mesh = _memo("mesh2", lambda: make_mesh(graph=2, batch=1))
    spg = _memo("spg2", lambda: _prepare_pull(_tiny_graph(), mesh, 64))
    ell0, folds = _memo("spg2_ell", lambda: device_ell_sharded(spg))
    return Program(
        name="sharded.pull_fused", path="bfs_tpu/parallel/sharded.py",
        fn=_bfs_sharded_pull_fused,
        args=(ell0, folds, jnp.int32(0)),
        static_kwargs=dict(mesh=mesh, block=spg.block, max_levels=16),
        v_elements=spg.num_vertices, budget_bytes=_hbm_envelope(),
        mesh_axes=frozenset({"graph"}),
        required_axes=frozenset({"graph"}),
        # (dist, parent, level): state distributed, level replicated.
        expected_out_names=(frozenset({"graph"}), frozenset({"graph"}),
                            frozenset()),
    )


def _spec_sharded_relay(flavor: str = "dense"):
    """The sharded relay program family (ISSUE 11): ``dense`` is the
    pull-only bitmap-arm baseline, ``exchange_auto`` compiles the
    word-list/bitmap density cond with the telemetry byte accumulators,
    and ``push`` ships the per-shard adjacency and the direction cond —
    all three must pass IR005/IR006 (collective axes + u32/i32 exchange
    payloads) and the donation/HBM rules."""
    from ..parallel.sharded import make_mesh

    _need_devices(2)
    from ..ops.packed import packed_rank_fits, resolve_packed
    from ..parallel.sharded import (
        _bfs_sharded_relay_fused,
        _own_word_table_dev,
        _prepare_relay,
        _relay_valid_words,
        _sharded_adj_dev,
        _sharded_adj_dummies,
        _sharded_relay_mask_args,
        _sharded_relay_static,
    )

    mesh = _memo("mesh2", lambda: make_mesh(graph=2, batch=1))
    srg = _memo("srg2", lambda: _prepare_relay(_tiny_graph(), mesh))
    packed = resolve_packed(packed_rank_fits(srg.in_classes))
    vperm_arg, net_arg = _sharded_relay_mask_args(srg, False)
    import jax.numpy as jnp

    static = _sharded_relay_static(srg, 2, False, packed)
    sparse = flavor == "push"
    if sparse:
        adj = _sharded_adj_dev(srg, packed)
        outdeg = jnp.asarray(srg.outdeg)
        direction = ("auto", 14.0, 24.0, srg.num_vertices, srg.num_edges)
    else:
        adj = _sharded_adj_dummies(2)
        outdeg = jnp.zeros((1,), jnp.int32)
        direction = None
    exchange = ("auto", 8) if flavor != "dense" else ("bitmap", 8)
    telemetry = flavor != "dense"
    return Program(
        name=f"sharded.relay_{flavor}", path="bfs_tpu/parallel/sharded.py",
        fn=_bfs_sharded_relay_fused,
        args=(
            vperm_arg, net_arg, _relay_valid_words(srg),
            _own_word_table_dev(srg), *adj, outdeg, jnp.int32(0),
        ),
        static_kwargs=dict(
            mesh=mesh, static=static, max_levels=16, telemetry=telemetry,
            direction=direction, exchange=exchange, sparse=sparse,
        ),
        v_elements=srg.num_vertices, packed=packed,
        budget_bytes=_hbm_envelope(),
        mesh_axes=frozenset({"graph", "batch"}),
        required_axes=frozenset({"graph"}),
    )


#: Push tiers small enough that the tiny graph's searches take both the
#: push arm and the sweep.
_TINY_PUSH_TIERS = ((8, 32), (32, 128))


def _spec_algo_sssp_fused(packed: bool, floating: bool = False):
    """The semiring SSSP programs (ISSUE 16): min-plus supersteps over the
    weight operand — the endpoint hash under an unpacked int32 or packed
    dist:16|parent:16 carry, or stored float32 weights under a float32
    carry (Graph500 kernel 3) — same HBM/donation rules as the BFS fused
    programs.  The unpacked programs compile the push arm beside the
    sweep (the out-row view and two tiers)."""
    import jax.numpy as jnp

    from ..algo.sssp import _sssp_fused

    dg, src, dst, w, ends, out = _memo("sssp_operands", _tiny_sssp_operands)
    v = dg.num_vertices
    if floating:
        from ..algo.sssp import _build_operands

        w = w.astype(jnp.float32) / 32
        ends, out = _build_operands(src, dst, w, num_slots=v + 1)
    flavor = "_packed" if packed else "_float" if floating else ""
    return Program(
        name=f"algo.sssp_fused{flavor}",
        path="bfs_tpu/algo/sssp.py",
        fn=_sssp_fused,
        args=(src, dst, w, ends, out, jnp.int32(0)),
        static_kwargs=dict(
            num_vertices=v, delta=float("inf") if floating else 64,
            max_rounds=64, packed=packed,
            tiers=() if packed else _TINY_PUSH_TIERS,
        ),
        v_elements=v, packed=packed, budget_bytes=_hbm_envelope(),
    )


def _tiny_sssp_operands():
    """The tiny graph's device edges with hash weights in ``[1, 31]``,
    each destination's last edge (the scan form of the combine) and the
    out-row view (the push arm)."""
    import jax.numpy as jnp

    from ..algo.sssp import _build_operands
    from ..algo.substrate import edge_weights
    from ..graph.csr import build_device_graph

    dg = _memo("dg", lambda: build_device_graph(_tiny_graph()))
    src, dst = jnp.asarray(dg.src), jnp.asarray(dg.dst)
    w = edge_weights(src, dst, 31)
    ends, out = _build_operands(src, dst, w, num_slots=dg.num_vertices + 1)
    return dg, src, dst, w, ends, out


def _spec_algo_sssp_segment():
    import jax.numpy as jnp

    from ..algo.sssp import _sssp_segment, init_sssp_state

    dg, src, dst, w, ends, out = _memo("sssp_operands", _tiny_sssp_operands)
    v = dg.num_vertices
    return Program(
        name="algo.sssp_segment", path="bfs_tpu/algo/sssp.py",
        fn=_sssp_segment,
        args=(init_sssp_state(v, 0, 64), jnp.int32(8), src, dst, w, ends,
              out),
        static_kwargs=dict(num_vertices=v, delta=64, packed=False,
                           tiers=_TINY_PUSH_TIERS),
        v_elements=v, donate={0: "state"}, budget_bytes=_hbm_envelope(),
    )


def _spec_algo_sssp_parents():
    """The exit-time parent pass — the one program every SSSP arm
    shares, which is WHY parents are schedule-independent."""
    import jax.numpy as jnp

    from ..algo.sssp import _sssp_parents

    dg, src, dst, w, ends, _ = _memo("sssp_operands", _tiny_sssp_operands)
    v = dg.num_vertices
    dist = jnp.zeros((v + 1,), jnp.int32)
    return Program(
        name="algo.sssp_parents", path="bfs_tpu/algo/sssp.py",
        fn=_sssp_parents,
        args=(dist, src, dst, w, ends, jnp.int32(0)),
        static_kwargs=dict(num_segments=v + 1),
        v_elements=v, budget_bytes=_hbm_envelope(),
    )


def _spec_algo_cc_fused(engine: str):
    from ..algo.cc import _cc_fused, _cc_pull_fused

    if engine == "pull":
        from ..graph.ell import build_pull_graph, device_ell

        pg = _memo("pg", lambda: build_pull_graph(_tiny_graph()))
        ell0, folds = _memo("ell", lambda: device_ell(pg))
        v = pg.num_vertices
        fn, args = _cc_pull_fused, (ell0, folds)
    else:
        import jax.numpy as jnp

        from ..graph.csr import build_device_graph

        dg = _memo("dg", lambda: build_device_graph(_tiny_graph()))
        v = dg.num_vertices
        fn, args = _cc_fused, (jnp.asarray(dg.src), jnp.asarray(dg.dst))
    return Program(
        name=f"algo.cc_fused_{engine}", path="bfs_tpu/algo/cc.py",
        fn=fn, args=args,
        static_kwargs=dict(num_vertices=v, max_rounds=v + 1),
        v_elements=v, budget_bytes=_hbm_envelope(),
    )


def _spec_algo_cc_segment():
    import jax.numpy as jnp

    from ..algo.cc import _cc_segment, init_cc_state
    from ..graph.csr import build_device_graph

    dg = _memo("dg", lambda: build_device_graph(_tiny_graph()))
    v = dg.num_vertices
    return Program(
        name="algo.cc_segment", path="bfs_tpu/algo/cc.py",
        fn=_cc_segment,
        args=(
            init_cc_state(v), jnp.int32(8),
            jnp.asarray(dg.src), jnp.asarray(dg.dst),
        ),
        static_kwargs=dict(num_vertices=v),
        v_elements=v, donate={0: "state"}, budget_bytes=_hbm_envelope(),
    )


def _spec_algo_sssp_sharded():
    import jax.numpy as jnp

    from ..parallel.sharded import make_mesh

    _need_devices(2)
    from ..algo.sharded import _sssp_sharded_fused
    from ..algo.sssp import SsspState
    from ..graph.csr import build_device_graph

    mesh = _memo("mesh2", lambda: make_mesh(graph=2, batch=1))
    dg = _memo(
        "dg2", lambda: build_device_graph(_tiny_graph(), num_shards=2)
    )
    v = dg.num_vertices
    return Program(
        name="algo.sssp_sharded", path="bfs_tpu/algo/sharded.py",
        fn=_sssp_sharded_fused,
        args=(
            jnp.asarray(dg.src).reshape(2, -1),
            jnp.asarray(dg.dst).reshape(2, -1),
            jnp.int32(0),
        ),
        static_kwargs=dict(
            mesh=mesh, num_vertices=v, max_weight=31, delta=64,
            max_rounds=64,
        ),
        v_elements=v, budget_bytes=_hbm_envelope(),
        mesh_axes=frozenset({"graph"}),
        required_axes=frozenset({"graph"}),
        # SsspState — every field replicated.
        expected_out_names=(frozenset(),) * len(SsspState._fields),
    )


def _spec_algo_cc_sharded():
    import jax.numpy as jnp

    from ..parallel.sharded import make_mesh

    _need_devices(2)
    from ..algo.sharded import _cc_sharded_fused
    from ..graph.csr import build_device_graph

    mesh = _memo("mesh2", lambda: make_mesh(graph=2, batch=1))
    dg = _memo(
        "dg2", lambda: build_device_graph(_tiny_graph(), num_shards=2)
    )
    v = dg.num_vertices
    return Program(
        name="algo.cc_sharded", path="bfs_tpu/algo/sharded.py",
        fn=_cc_sharded_fused,
        args=(
            jnp.asarray(dg.src).reshape(2, -1),
            jnp.asarray(dg.dst).reshape(2, -1),
        ),
        static_kwargs=dict(mesh=mesh, num_vertices=v, max_rounds=64),
        v_elements=v, budget_bytes=_hbm_envelope(),
        mesh_axes=frozenset({"graph"}),
        required_axes=frozenset({"graph"}),
        # CcState(label, frontier, rounds, changed) — replicated.
        expected_out_names=(frozenset(),) * 4,
    )


#: name -> builder.  Order is the report order.
def _spec_stream_sb_expand():
    """The streamed arm's per-superblock expansion program (ISSUE 18):
    one column superblock's tiles expanded into the candidate grid — the
    candidate carry is donated (callers chain ``cand2d = prog(cand2d,
    ...)``), the streamed operands are the cache's device slabs, and the
    math is the resident XLA twin's per-chunk body with a local
    segment-min, so dtype/transfer/footprint rules must hold exactly as
    for the resident expansion."""
    import jax.numpy as jnp
    import numpy as np

    from ..stream.prefetch import frontier_blocks
    from ..stream.runner import _cand_init_program, _sb_expand_program
    from ..stream.store import HostTileStore

    eng = _relay_engine_mxu()
    at = eng.adj_tiles
    store = HostTileStore(at)
    tiles, row_idx, col_local = store.fetch(0)
    fwords = np.zeros(at.rows // 32 + (1 if at.rows % 32 else 0),
                      dtype=np.uint32)
    fwords[0] = 1
    return Program(
        name="stream.sb_expand", path="bfs_tpu/stream/runner.py",
        fn=_sb_expand_program(store.pad_tiles(0)),
        args=(
            _cand_init_program(at.vtp)(),
            jnp.asarray(frontier_blocks(fwords, at.rtp)),
            jnp.asarray(store.keys2d),
            jnp.asarray(tiles), jnp.asarray(row_idx),
            jnp.asarray(col_local), jnp.int32(0),
        ),
        v_elements=eng.relay_graph.vr, packed=eng.packed,
        donate={0: "cand2d"},
    )


PROGRAM_SPECS = {
    "bfs.push_fused": _spec_push_fused,
    "bfs.pull_fused": _spec_pull_fused,
    "serve.batch_push": lambda: _spec_serve_batch("push"),
    "serve.batch_pull": lambda: _spec_serve_batch("pull"),
    "serve.label_lookup": _spec_label_lookup,
    "direction.fused_auto": _spec_direction_fused,
    "relay.fused": _spec_relay_fused,
    "relay.fused_mxu": _spec_relay_fused_mxu,
    "relay.multi_fused": _spec_relay_multi_fused,
    "relay.step_dense": lambda: _spec_relay_step("dense"),
    "relay.step_sparse": lambda: _spec_relay_step("sparse"),
    "relay.segment": _spec_relay_segment,
    "relay.segment_mxu": _spec_relay_segment_mxu,
    "stream.sb_expand": _spec_stream_sb_expand,
    "multisource.segment_push": lambda: _spec_multi_segment("push"),
    "multisource.segment_pull": lambda: _spec_multi_segment("pull"),
    "sharded.relay_segment": _spec_sharded_relay_segment,
    "superstep.push_step": lambda: _spec_superstep("push"),
    "superstep.pull_step": lambda: _spec_superstep("pull"),
    "sharded.push_fused": _spec_sharded_push,
    "sharded.pull_fused": _spec_sharded_pull,
    "sharded.relay_dense": lambda: _spec_sharded_relay("dense"),
    "sharded.relay_exchange_auto": lambda: _spec_sharded_relay(
        "exchange_auto"
    ),
    "sharded.relay_push": lambda: _spec_sharded_relay("push"),
    "sharded.relay_mxu": _spec_sharded_relay_mxu,
    "grid.relay_bitmap": lambda: _spec_grid_relay("bitmap"),
    "grid.relay_auto": lambda: _spec_grid_relay("auto"),
    "grid.segment": _spec_grid_segment,
    "algo.sssp_fused": lambda: _spec_algo_sssp_fused(False),
    "algo.sssp_fused_packed": lambda: _spec_algo_sssp_fused(True),
    "algo.sssp_fused_float": lambda: _spec_algo_sssp_fused(False, True),
    "algo.sssp_segment": _spec_algo_sssp_segment,
    "algo.sssp_parents": _spec_algo_sssp_parents,
    "algo.cc_fused_push": lambda: _spec_algo_cc_fused("push"),
    "algo.cc_fused_pull": lambda: _spec_algo_cc_fused("pull"),
    "algo.cc_segment": _spec_algo_cc_segment,
    "algo.sssp_sharded": _spec_algo_sssp_sharded,
    "algo.cc_sharded": _spec_algo_cc_sharded,
    "layout.device_hist": lambda: _spec_layout_device("layout.device_hist"),
    "layout.device_relabel": lambda: _spec_layout_device(
        "layout.device_relabel"
    ),
    "layout.device_slots": lambda: _spec_layout_device("layout.device_slots"),
    "layout.device_net_assembly": lambda: _spec_layout_device(
        "layout.device_net_assembly"
    ),
    "layout.device_vperm_assembly": lambda: _spec_layout_device(
        "layout.device_vperm_assembly"
    ),
    "layout.device_csr": lambda: _spec_layout_device("layout.device_csr"),
    "layout.device_compact": lambda: _spec_layout_device(
        "layout.device_compact"
    ),
    "layout.route_level": lambda: _spec_layout_device("layout.route_level"),
    "layout.route_mid": lambda: _spec_layout_device("layout.route_mid"),
}


# --------------------------------------------------------------------------
# Content-addressed result cache + the repo entry point.
# --------------------------------------------------------------------------

def repo_root() -> str:
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(here))


def _ensure_jax_env() -> None:
    """CLI runs get the test harness's virtual multi-device CPU platform
    (the mesh programs need >= 2 devices).  The ``python -m`` and
    console-script spellings import the parent package (and thus jax)
    before this runs, so "jax already imported" is not the boundary —
    "backend already initialized" is: platform and device count are read
    lazily at first backend init, and config/env set before that still
    take effect.  A caller who explicitly set ``JAX_PLATFORMS`` or an
    initialized backend (tests, library use) is left alone."""
    def _add_device_flag():
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    if "jax" not in sys.modules:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        _add_device_flag()
        return
    import jax

    try:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            return
    except (ImportError, AttributeError):
        return  # cannot tell — do not disturb a possibly-live backend
    # The device-count flag only affects the host (CPU) platform, so it
    # is safe regardless of the platform choice below.
    _add_device_flag()
    if not os.environ.get("JAX_PLATFORMS"):
        jax.config.update("jax_platforms", "cpu")


def _source_fingerprint(root: str) -> str:
    h = hashlib.blake2b(digest_size=16)
    pkg = os.path.join(root, "bfs_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = sorted(
            d for d in dirnames
            if d != "__pycache__" and not d.startswith(".")
        )
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            p = os.path.join(dirpath, fn)
            h.update(os.path.relpath(p, root).encode())
            with open(p, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def _cache_key(root: str) -> str:
    import jax

    h = hashlib.blake2b(digest_size=16)
    h.update(_source_fingerprint(root).encode())
    h.update(jax.__version__.encode())
    h.update(jax.default_backend().encode())
    h.update(str(len(jax.devices())).encode())
    h.update(str(IR_VERSION).encode())
    h.update(",".join(sorted(PROGRAM_SPECS)).encode())
    for env in _FLAVOR_ENV:
        h.update(f"{env}={os.environ.get(env, '')};".encode())
    return h.hexdigest()


def default_cache_dir(root: str | None = None) -> str:
    env = knobs.raw("BFS_TPU_IR_CACHE") or ""
    if env:
        return env
    return os.path.join(root or repo_root(), ".bench_cache", "ir")


def _finding_to_dict(f: Finding) -> dict:
    return {
        "rule": f.rule, "path": f.path, "line": f.line, "col": f.col,
        "message": f.message, "snippet": f.snippet,
    }


def analyze_ir(
    specs: dict | None = None,
    *,
    use_cache: bool = True,
    cache_dir: str | None = None,
    root: str | None = None,
) -> tuple[list[Finding], dict]:
    """Run the IR pass.  Returns ``(findings, meta)`` where ``meta``
    records cache disposition and skipped programs.  ``specs`` overrides
    the registry (tests feed fixture programs); custom specs are never
    cached — only the canonical repo registry is content-addressed."""
    _ensure_jax_env()
    root = root or repo_root()
    custom = specs is not None
    specs = specs if custom else PROGRAM_SPECS
    meta: dict = {"cache": "off" if (custom or not use_cache) else "miss",
                  "programs": [], "skipped": {}}

    cache_path = None
    if not custom and use_cache:
        key = _cache_key(root)
        cache_path = os.path.join(
            cache_dir or default_cache_dir(root), f"ir_{key}.json"
        )
        if os.path.exists(cache_path):
            try:
                with open(cache_path, encoding="utf-8") as fh:
                    doc = json.load(fh)
                meta.update(doc.get("meta", {}))
                meta["cache"] = "hit"
                return [Finding(**d) for d in doc["findings"]], meta
            except (ValueError, KeyError, TypeError):
                pass  # corrupt cache entry: recompute and overwrite

    findings: list[Finding] = []
    for name, build in specs.items():
        try:
            prog = build()
            result = analyze_program(prog)
        except SkipProgram as exc:
            meta["skipped"][name] = str(exc)
            continue
        except Exception as exc:
            findings.append(Finding(
                rule="IR000", path="bfs_tpu/analysis/ir.py", line=0, col=0,
                message=f"[{name}] spec builder failed: "
                        f"{type(exc).__name__}: {exc}",
                snippet=f"ir:{name}:builder",
            ))
            continue
        meta["programs"].append(name)
        findings.extend(result)

    findings.sort(key=lambda f: (f.path, f.rule, f.snippet))
    if cache_path is not None:
        try:
            os.makedirs(os.path.dirname(cache_path), exist_ok=True)
            tmp = f"{cache_path}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(
                    {"meta": {k: v for k, v in meta.items()
                              if k != "cache"},
                     "findings": [_finding_to_dict(f) for f in findings]},
                    fh,
                )
            os.replace(tmp, cache_path)
        except OSError:
            pass
    return findings, meta
