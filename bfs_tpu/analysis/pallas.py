"""Kernel-grade static analysis: run every hand-written Pallas kernel at
lint scale and prove the invariants Mosaic will not check for us.

The fourth analyzer rung.  AST (:mod:`.core`) polices what the SOURCE
says, jaxpr (:mod:`.ir`) what we ASK XLA to do, HLO (:mod:`.hlo`) what
XLA EMITS — but the ~1.2k lines of hand-written Pallas kernels in
``ops/relay_pallas.py`` are opaque to all three: a jaxpr walk sees one
``pallas_call`` eqn, the optimized HLO one ``custom-call``-shaped
kernel, and neither knows the kernel's VMEM budget, its grid's output
partition, or whether its manual-DMA windows stay inside the mask
arrays.  Those properties are exactly the ones that fail ONLY on real
TPUs (Mosaic OOMs VMEM, a mis-partitioned grid races, a stale stage
table DMAs past its array) — so they need a compile-free gate that runs
in tier-1 on CPU.

Mechanism: a :data:`KERNEL_SPECS` registry (set-equality-pinned against
every ``pl.pallas_call`` site discovered by AST in ``bfs_tpu/`` — an
unregistered kernel fails lint AND tier-1) whose entries build tiny
deterministic operands and invoke the SHIPPING wrapper functions in
interpret mode under a ``pallas_call`` spy.  The spy records each call's
grid, BlockSpecs, out shapes and scratch allocations — the real
parameters the real code computed, not a re-derivation — and the rules
walk the records:

* **PAL001 VMEM residency proof** — per captured call: grid-blocked
  operand/output blocks are double-buffered by the Pallas pipeline
  (2x block bytes each) and explicit VMEM scratch counted at its full
  declared shape (DMA depth is already in the shape), summed against
  ``BFS_TPU_PAL_VMEM_MB`` (default 16 MB/core).  Reported per kernel
  like the IR004 HBM proof; the bench-scale derivation lives in
  ARCHITECTURE §21.
* **PAL002 tile alignment** — every blocked dimension checked against
  the (8, 128) sublane/lane tiling for its dtype (16/32 sublanes for
  2/1-byte types); specs flagged ``mxu=True`` (the ROADMAP item 2
  expansion arm) must additionally tile to the 128x128 MXU.
* **PAL003 grid write-aliasing** — each output BlockSpec's index map is
  evaluated over every grid step; two steps mapping the same output
  block is the data race ``pl.when``-guarded stores can hide (errors
  unless the spec declares accumulation), and a block no step writes is
  garbage output.
* **PAL004 dynamic-slice bounds** — auto half: every grid-blocked input
  block must lie inside its operand and the grid must cover the whole
  array (a ``tile_rows`` that does not divide the row count silently
  drops the tail — the ADVICE r4 bug class).  Manual half: the spec
  supplies the kernels' ``pl.ds`` DMA windows (computed from the SAME
  static stage tables the kernels consume, via the ``*_windows``
  helpers below) and every window must fit its mask array.
* **PAL005 interpret-vs-XLA parity oracle** — the dynamic leg: the
  captured interpret-mode result is compared bit-identical against the
  kernel's shipping XLA fallback twin (``ops/relay.rowmin_ranks``,
  ``apply_relay_candidates_packed``, ``apply_benes_std``,
  ``relay_elem.apply_benes_elem``).  A kernel whose twin disagrees at
  lint scale is wrong on every TPU.

Like the IR/HLO rungs this module imports jax and is loaded only by the
``--pallas`` CLI path and its tests.  The cold run costs ~20 s of
interpret-mode execution, so results are content-addressed exactly like
the other rungs (sources + jax version/backend/devices + PAL_VERSION +
flavor env; ``.bench_cache/pal/``, ``BFS_TPU_PAL_CACHE``).  Findings
share ``baseline.txt`` with line-drift-proof ``pal:<kernel>:<detail>``
fingerprints and the unified stale-entry semantics.
"""

from __future__ import annotations

import ast
import hashlib
import json
import math
import os
from dataclasses import dataclass, field

from .. import knobs
from .core import Finding
from .ir import (
    SkipProgram,
    _ensure_jax_env,
    _source_fingerprint,
    repo_root,
)

#: Bump to invalidate every cached Pallas result (rule semantics changed).
PAL_VERSION = 1

#: Env knobs that change kernel flavors/shapes — DERIVED from the
#: registry (``affects`` contains ``pal``): the IR flavor set plus the
#: relay_pallas module constants (read at import) and the VMEM budget
#: rule input.  KNB002 proves membership against bfs_tpu/knobs.py.
_PAL_FLAVOR_ENV = knobs.flavor_env("pal")


def vmem_budget_bytes() -> int:
    """Per-core VMEM budget the PAL001 proof checks against.
    ``BFS_TPU_PAL_VMEM_MB`` overrides (e.g. proving a raised
    scoped-vmem config); the default is the classic 16 MB/core."""
    return int(knobs.get("BFS_TPU_PAL_VMEM_MB") * (1 << 20))


# --------------------------------------------------------------------------
# Specs: one registered kernel = one shipping wrapper invocation.
# --------------------------------------------------------------------------

@dataclass
class Window:
    """One manual-DMA window (a ``pl.ds`` row slice) PAL004 must prove
    in-bounds: rows ``[start, start+size)`` of a ``limit``-row ref."""

    label: str
    start: int
    size: int
    limit: int


@dataclass
class KernelCase:
    """One built kernel invocation plus its declared contracts.

    ``run()`` must invoke the shipping wrapper(s) so the ``pallas_call``
    spy captures the real grid/BlockSpecs; ``twin()`` (optional) is the
    XLA fallback the PAL005 oracle diffs against, bit-identical.
    """

    run: object  # () -> result pytree (executed under the capture spy)
    twin: object = None  # () -> the XLA twin's result pytree, or None
    #: manual-DMA windows for PAL004 (refs the kernel slices itself)
    windows: list = field(default_factory=list)
    #: grid steps may write the same output block on purpose (reductions)
    accumulates: bool = False
    #: blocks must tile the 128x128 MXU (the expansion-arm contract)
    mxu: bool = False


@dataclass
class KernelSpec:
    """Registry entry: which ``pallas_call`` sites this kernel covers and
    how to build its lint-scale case."""

    name: str
    path: str  # repo-relative source anchor for findings
    sites: tuple  # ("bfs_tpu/ops/relay_pallas.py::fn", ...) covered
    build: object  # () -> KernelCase


# --------------------------------------------------------------------------
# The pallas_call spy: capture the REAL call parameters.
# --------------------------------------------------------------------------

@dataclass
class SpecInfo:
    """One BlockSpec paired with the array it blocks."""

    block_shape: tuple | None  # None = unblocked (memory_space ref)
    index_map: object
    array_shape: tuple
    itemsize: int
    label: str  # "in0" / "out1" — the finding detail anchor


@dataclass
class CallRecord:
    """One captured ``pl.pallas_call`` invocation."""

    kernel_name: str
    grid: tuple
    in_specs: list
    out_specs: list
    scratch_bytes: int  # explicit VMEM scratch (semaphores excluded)
    scratch_shapes: list  # [(shape, dtype_str), ...] for reporting
    interpret: bool
    #: non-None = the call used a parameter shape the spy cannot decode
    #: (e.g. grid_spec=) — the rules would run vacuously, so analyze
    #: turns this into a loud PAL000 instead of a silent green.
    undecoded: str | None = None


def _leaves(x):
    import jax

    return jax.tree_util.tree_leaves(x)


def _spec_infos(specs, arrays, label: str) -> list:
    import numpy as np

    out = []
    for i, (bs, arr) in enumerate(zip(specs, arrays)):
        block = getattr(bs, "block_shape", None)
        shape = tuple(getattr(arr, "shape", ()))
        dtype = getattr(arr, "dtype", None)
        itemsize = int(np.dtype(dtype).itemsize) if dtype is not None else 4
        if block is not None:
            # None elements mean "whole dimension" in a BlockSpec.
            block = tuple(
                int(d) if b is None else int(b)
                for b, d in zip(block, shape)
            )
        out.append(SpecInfo(
            block_shape=block, index_map=getattr(bs, "index_map", None),
            array_shape=shape, itemsize=itemsize, label=f"{label}{i}",
        ))
    return out


def _scratch_info(scratch_shapes) -> tuple[int, list]:
    import numpy as np

    total, shapes = 0, []
    for s in scratch_shapes or ():
        shape = tuple(getattr(s, "shape", ()))
        dtype = getattr(s, "dtype", None)
        name = str(dtype)
        if "sem" in name:  # semaphores occupy semaphore memory, not VMEM
            continue
        try:
            itemsize = int(np.dtype(dtype).itemsize)
        except TypeError:
            itemsize = 4
        total += int(math.prod(shape)) * itemsize
        shapes.append((shape, name))
    return total, shapes


def capture_pallas_calls(fn):
    """Run ``fn()`` with ``pl.pallas_call`` wrapped so every invocation's
    real parameters are recorded.  The kernels import pallas inside their
    function bodies, so patching the module attribute is seen by every
    call.  Returns ``(result, [CallRecord, ...])``."""
    from jax.experimental import pallas as pl

    records: list[CallRecord] = []
    real = pl.pallas_call

    def spy(kernel, **kwargs):
        inner = real(kernel, **kwargs)

        def call(*operands):
            grid = kwargs.get("grid", ())
            if isinstance(grid, int):
                grid = (grid,)
            in_specs = list(kwargs.get("in_specs", ()) or ())
            out_spec_leaves = _leaves(kwargs.get("out_specs"))
            out_shape_leaves = _leaves(kwargs.get("out_shape"))
            scratch_bytes, scratch_shapes = _scratch_info(
                kwargs.get("scratch_shapes")
            )
            undecoded = None
            if kwargs.get("grid_spec") is not None:
                # grid/in_specs/out_specs live inside the grid_spec
                # object; the rules above would all run over EMPTY spec
                # lists and pass vacuously on a kernel that is anything
                # but policed.
                undecoded = "grid_spec="
            records.append(CallRecord(
                kernel_name=getattr(kernel, "__name__", "<kernel>"),
                grid=tuple(int(g) for g in grid),
                in_specs=_spec_infos(in_specs, operands, "in"),
                out_specs=_spec_infos(
                    out_spec_leaves, out_shape_leaves, "out"
                ),
                scratch_bytes=scratch_bytes,
                scratch_shapes=scratch_shapes,
                interpret=bool(kwargs.get("interpret", False)),
                undecoded=undecoded,
            ))
            return inner(*operands)

        return call

    pl.pallas_call = spy
    try:
        result = fn()
    finally:
        pl.pallas_call = real
    return result, records


# --------------------------------------------------------------------------
# Manual-DMA window enumeration: the kernels' `pl.ds` arithmetic over the
# static stage tables.  This is the ONE deliberate duplication of the
# kernels' offset formulas (st.offset // LANES + pid * rows) — PAL005's
# bit-parity run proves the kernels themselves; these windows prove the
# STATIC TABLES they consume (a stale/corrupt stage table whose offsets
# run past the prepared mask arrays is exactly what PAL004 catches).
# --------------------------------------------------------------------------

def benes_word_windows(pass_static_info, array_rows: list, n: int) -> list:
    """Every mask-DMA window of :func:`ops.relay_pallas.apply_benes_fused`
    for one prepared layout.  ``array_rows``: row counts of the prepared
    mask arrays in ``prepare_pass_masks`` order."""
    from ..ops.relay_pallas import LANES, _is_lane_compact, _stage_rows

    windows: list[Window] = []
    r = n // 32 // LANES
    ai = 0
    for mode, tr, tt, specs in pass_static_info:
        main_rows = array_rows[ai]
        ai += 1
        lane_rows = None
        if mode == "local" and any(_is_lane_compact(st) for st in specs):
            lane_rows = array_rows[ai]
            ai += 1
        if mode == "local_tm":
            block_rows = sum(_stage_rows(st, tr) for st in specs)
            for t in range(max(r // tr, 1)):
                windows.append(Window(
                    f"tm:tile{t}", t * block_rows, block_rows, main_rows
                ))
        elif mode == "local":
            for pid in range(r // tr):
                for st in specs:
                    rows = _stage_rows(st, tr)
                    limit = (
                        lane_rows if _is_lane_compact(st) else main_rows
                    )
                    windows.append(Window(
                        f"local:d{st.d}:p{pid}",
                        st.offset // LANES + pid * rows, rows, limit,
                    ))
        else:  # outer
            span = (r // tr) // 2  # outer stages are always pair-compact
            rows = span * tt
            for pid in range(tr // tt):
                for st in specs:
                    windows.append(Window(
                        f"outer:d{st.d}:p{pid}",
                        st.offset // LANES + pid * rows, rows, main_rows,
                    ))
    return windows


def benes_elem_windows(pass_static_info, array_rows: list, n: int) -> list:
    """Mask-DMA windows of :func:`ops.relay_pallas.apply_benes_elem_fused`
    (vertically-packed masks: one stored row per 32 element rows)."""
    from ..ops.relay_pallas import LANES

    windows: list[Window] = []
    r = n // LANES
    for ai, (mode, tr, tt, specs, m) in enumerate(pass_static_info):
        main_rows = array_rows[ai]
        if mode == "local":
            for pid in range(r // tr):
                for st in specs:
                    mrows = (tr // 2 if st.compact else tr) // 32
                    windows.append(Window(
                        f"elem-local:d{st.d}:p{pid}",
                        st.offset // LANES + pid * mrows, mrows, main_rows,
                    ))
        else:  # outer: grid (hi, tr/tt), one (m/2, tt/32) block per step
            mrows = (m // 2) * (tt // 32)
            for pid in range((r // (tr * m)) * (tr // tt)):
                for st in specs:
                    windows.append(Window(
                        f"elem-outer:d{st.d}:p{pid}",
                        st.offset // LANES + pid * mrows, mrows, main_rows,
                    ))
    return windows


# --------------------------------------------------------------------------
# Per-kernel analysis.
# --------------------------------------------------------------------------

def tree_bit_identical(a, b):
    """``(ok, detail)`` — every leaf bit-identical in shape, dtype and
    value.  The PAL005 contract: the fused kernels are drop-in twins of
    their XLA fallbacks, not approximations."""
    import numpy as np

    la, lb = _leaves(a), _leaves(b)
    if len(la) != len(lb):
        return False, f"leaf count {len(la)} != {len(lb)}"
    for i, (x, y) in enumerate(zip(la, lb)):
        xa, ya = np.asarray(x), np.asarray(y)
        if xa.shape != ya.shape:
            return False, f"leaf {i}: shape {xa.shape} != {ya.shape}"
        if xa.dtype != ya.dtype:
            return False, f"leaf {i}: dtype {xa.dtype} != {ya.dtype}"
        # Raw-byte comparison, not value equality: -0.0 == 0.0 and
        # NaN != NaN would both misjudge a float kernel's parity
        # (review finding) — the contract is the BITS agree.
        ba, bb = xa.tobytes(), ya.tobytes()
        if ba != bb:
            n = max(xa.size, 1)
            va = np.frombuffer(ba, np.uint8).reshape(n, -1)
            vb = np.frombuffer(bb, np.uint8).reshape(n, -1)
            neq = (va != vb).any(axis=1)
            return False, (
                f"leaf {i}: {int(neq.sum())}/{n} elements differ "
                f"bit-wise (first at flat index {int(np.argmax(neq))})"
            )
    return True, ""


def analyze_kernel(spec: KernelSpec) -> list:
    """All PAL findings for one registered kernel (deduped, sorted)."""
    from .pallas_rules import check_kernel

    def make_finding(rule: str, detail: str, message: str) -> Finding:
        return Finding(
            rule=rule, path=spec.path, line=0, col=0,
            message=f"[{spec.name}] {message}",
            snippet=f"pal:{spec.name}:{detail}",
        )

    try:
        case = spec.build()
        result, records = capture_pallas_calls(case.run)
    except SkipProgram:
        raise
    except Exception as exc:
        return [make_finding(
            "PAL000", "build",
            f"could not build/run the kernel case: "
            f"{type(exc).__name__}: {exc}",
        )]
    findings = []
    if not records:
        findings.append(make_finding(
            "PAL000", "no-pallas-call",
            "the case ran without invoking pl.pallas_call — the spec no "
            "longer exercises its kernel (fallback path taken?)",
        ))
    for rec in records:
        if rec.undecoded is not None:
            findings.append(make_finding(
                "PAL000", f"undecoded:{rec.kernel_name}",
                f"kernel '{rec.kernel_name}' passes {rec.undecoded} to "
                "pallas_call, which the capture spy cannot decode — the "
                "static rules would run over empty spec lists and pass "
                "vacuously; extend capture_pallas_calls before "
                "registering this kernel shape",
            ))
    findings += check_kernel(spec, case, records, make_finding)
    if case.twin is not None:
        try:
            expected = case.twin()
        except Exception as exc:
            findings.append(make_finding(
                "PAL000", "twin",
                f"XLA twin failed to run: {type(exc).__name__}: {exc}",
            ))
        else:
            ok, detail = tree_bit_identical(result, expected)
            if not ok:
                findings.append(make_finding(
                    "PAL005", "parity",
                    f"interpret-mode kernel output is NOT bit-identical "
                    f"to its XLA fallback twin: {detail} — the fused "
                    "kernel and the fallback disagree, so one of them "
                    "is wrong on every backend that selects it",
                ))
    seen, out = set(), []
    for f in findings:
        key = (f.rule, f.snippet)
        if key not in seen:
            seen.add(key)
            out.append(f)
    out.sort(key=lambda f: (f.path, f.rule, f.snippet))
    return out


# --------------------------------------------------------------------------
# Site discovery + the set-equality pin.
# --------------------------------------------------------------------------

def discover_pallas_sites(root: str | None = None) -> set:
    """Every ``pl.pallas_call`` call site in ``bfs_tpu/`` as
    ``"<repo-relative path>::<enclosing function>"``.  AST-based and
    stdlib-only: the pin must see sites even in modules that fail to
    import."""
    root = root or repo_root()
    pkg = os.path.join(root, "bfs_tpu")
    sites: set[str] = set()
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = sorted(
            d for d in dirnames
            if d != "__pycache__" and not d.startswith(".")
        )
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            try:
                with open(path, encoding="utf-8") as fh:
                    tree = ast.parse(fh.read(), filename=rel)
            except SyntaxError:
                continue
            stack: list[str] = []

            def walk(node):
                for child in ast.iter_child_nodes(node):
                    if isinstance(
                        child, (ast.FunctionDef, ast.AsyncFunctionDef)
                    ):
                        stack.append(child.name)
                        walk(child)
                        stack.pop()
                        continue
                    if isinstance(child, ast.Call):
                        f = child.func
                        name = (
                            f.attr if isinstance(f, ast.Attribute)
                            else getattr(f, "id", "")
                        )
                        if name == "pallas_call":
                            owner = stack[0] if stack else "<module>"
                            sites.add(f"{rel}::{owner}")
                    walk(child)

            walk(tree)
    return sites


def registry_findings(specs: dict, root: str | None = None) -> list:
    """The set-equality pin as lint findings: every discovered
    ``pallas_call`` site must be covered by a spec, and every spec site
    must still exist."""
    discovered = discover_pallas_sites(root)
    covered: set[str] = set()
    for spec_build in specs.values():
        covered.update(getattr(spec_build, "sites", ()))
    findings = []
    for site in sorted(discovered - covered):
        findings.append(Finding(
            rule="PAL000", path=site.split("::")[0], line=0, col=0,
            message=(
                f"pallas_call site '{site}' has no KERNEL_SPECS entry — "
                "an unregistered kernel is an unpoliced kernel; add a "
                "spec covering it (bfs_tpu/analysis/pallas.py)"
            ),
            snippet=f"pal:registry:unregistered:{site}",
        ))
    for site in sorted(covered - discovered):
        findings.append(Finding(
            rule="PAL000", path=site.split("::")[0], line=0, col=0,
            message=(
                f"KERNEL_SPECS covers site '{site}' which no longer "
                "exists — prune or update the spec"
            ),
            snippet=f"pal:registry:missing:{site}",
        ))
    return findings


def registered_sites(specs: dict | None = None) -> set:
    specs = specs if specs is not None else KERNEL_SPECS
    out: set[str] = set()
    for build in specs.values():
        out.update(getattr(build, "sites", ()))
    return out


# --------------------------------------------------------------------------
# The kernel registry: every shipped pallas_call site, built tiny.
# --------------------------------------------------------------------------

_PAL_PATH = "bfs_tpu/ops/relay_pallas.py"
_MXU_PATH = "bfs_tpu/ops/relay_mxu.py"
_BUILD_CACHE: dict = {}


def _memo(key, build):
    if key not in _BUILD_CACHE:
        _BUILD_CACHE[key] = build()
    return _BUILD_CACHE[key]


class _forced_env:
    """Deterministically pin flavor env inside a spec builder (the
    lane-compact spec must build its pass layout with the knob ON no
    matter the ambient env, and restore on exit)."""

    def __init__(self, **env):
        self.env = env
        self.saved: dict = {}

    def __enter__(self):
        for k, v in self.env.items():
            self.saved[k] = os.environ.get(k)
            os.environ[k] = v
        return self

    def __exit__(self, *exc):
        for k, old in self.saved.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old


def _routed_words(n: int, seed: int):
    """A routed Beneš layout at lint scale: (masks, table, packed input
    words, unpacked reference bits).  Requires the native router (the
    jax route arm exists but the walker is the pinned oracle) — skipped
    when unavailable, like the mesh programs below 2 devices."""
    def build():
        import numpy as np

        from ..graph import benes
        from ..graph.relay import _compact_and_table

        if not benes.native_available():
            raise SkipProgram("native benes router unavailable")
        rng = np.random.default_rng(seed)
        perm = rng.permutation(n).astype(np.int64)
        masks, table = _compact_and_table(benes.route_std(perm), n)
        bits = rng.integers(0, 2, size=n).astype(np.uint8)
        return masks, table, bits

    return _memo(("routed", n, seed), build)


#: Word-pass lint scale: r = n/32/128 = 32 rows; tile_rows=16 keeps two
#: local tiles AND leaves the d >= tr*4096 stages to the outer passes,
#: so one case exercises both the tile-major local kernel and the
#: outer-pass kernel of _run_pass.
_WORD_N = 1 << 17
_WORD_TR = 16


def _spec_benes_word_tile_major() -> KernelCase:
    import jax.numpy as jnp

    from ..ops.relay import apply_benes_std, pack_std
    from ..ops import relay_pallas as RP

    masks, table, bits = _routed_words(_WORD_N, 5)
    with _forced_env(BFS_TPU_LANE_COMPACT="0"):
        ps = RP.pass_static(table, _WORD_N, tile_rows=_WORD_TR)
        arrays = [
            jnp.asarray(a)
            for a in RP.prepare_pass_masks(
                masks, table, _WORD_N, tile_rows=_WORD_TR
            )
        ]
    x = pack_std(jnp.asarray(bits))
    return KernelCase(
        run=lambda: RP.apply_benes_fused(
            x, arrays, ps, _WORD_N, interpret=True
        ),
        twin=lambda: apply_benes_std(
            x, jnp.asarray(masks), table, _WORD_N
        ),
        windows=benes_word_windows(
            ps, [int(a.shape[0]) for a in arrays], _WORD_N
        ),
    )


def _spec_benes_word_lane_compact() -> KernelCase:
    import jax.numpy as jnp

    from ..ops.relay import apply_benes_std, pack_std
    from ..ops import relay_pallas as RP

    masks, table, bits = _routed_words(_WORD_N, 5)
    with _forced_env(BFS_TPU_LANE_COMPACT="1"):
        ps = RP.pass_static(table, _WORD_N, tile_rows=_WORD_TR)
        arrays = [
            jnp.asarray(a)
            for a in RP.prepare_pass_masks(
                masks, table, _WORD_N, tile_rows=_WORD_TR
            )
        ]
        local = next(sp for m, _t, _tt, sp in ps if m == "local")
        if not any(RP._is_lane_compact(st) for st in local):
            raise SkipProgram(
                "no lane-compactable stage at lint scale — the "
                "per-stage path is not exercised"
            )
    x = pack_std(jnp.asarray(bits))
    return KernelCase(
        run=lambda: RP.apply_benes_fused(
            x, arrays, ps, _WORD_N, interpret=True
        ),
        twin=lambda: apply_benes_std(
            x, jnp.asarray(masks), table, _WORD_N
        ),
        windows=benes_word_windows(
            ps, [int(a.shape[0]) for a in arrays], _WORD_N
        ),
    )


#: Element-pass lint scale: r = n/128 = 64 element rows; tile_rows=32
#: forces outer prefix/suffix passes around a 2-tile local run.
_ELEM_N = 1 << 13
_ELEM_TR = 32
_ELEM_TT = 32


def _spec_benes_elem() -> KernelCase:
    def build_ops():
        import numpy as np

        import jax.numpy as jnp

        from ..ops import relay_pallas as RP

        masks, table, _bits = _routed_words(_ELEM_N, 9)
        ps = RP.elem_pass_static(
            table, _ELEM_N, tile_rows=_ELEM_TR, outer_tt=_ELEM_TT
        )
        arrays = [
            jnp.asarray(a)
            for a in RP.prepare_elem_pass_masks(
                masks, table, _ELEM_N, tile_rows=_ELEM_TR,
                outer_tt=_ELEM_TT,
            )
        ]
        rng = np.random.default_rng(13)
        x = jnp.asarray(
            rng.integers(0, 2**32, (2, _ELEM_N), dtype=np.uint32)
        )
        return masks, table, ps, arrays, x

    import jax.numpy as jnp

    from ..ops import relay_pallas as RP
    from ..ops.relay_elem import apply_benes_elem

    masks, table, ps, arrays, x = _memo("elem_case", build_ops)
    return KernelCase(
        run=lambda: RP.apply_benes_elem_fused(
            x, arrays, ps, _ELEM_N, interpret=True
        ),
        twin=lambda: apply_benes_elem(
            x, jnp.asarray(masks), table, _ELEM_N
        ),
        windows=benes_elem_windows(
            ps, [int(a.shape[0]) for a in arrays], _ELEM_N
        ),
    )


def _rowmin_case():
    """Synthetic class layout for the tournament: one fused-eligible
    rank-major class (width 4 — the narrow widths real degree classes
    produce), one vertex-major class on the XLA fallback, and a sentinel
    tail past the last class — the three per-class paths of
    rowmin_ranks_pallas in one call."""
    def build():
        import numpy as np

        import jax.numpy as jnp

        from ..graph.relay import ClassSlice

        a = ClassSlice(width=4, va=0, vb=4096, sa=0, sb=4 * 4096,
                       real=4096, vertex_major=False, real_width=4)
        b = ClassSlice(width=64, va=4096, vb=4096 + 32, sa=4 * 4096,
                       sb=4 * 4096 + 32 * 64, real=32, vertex_major=True,
                       real_width=64)
        rng = np.random.default_rng(17)
        nwords = b.sb // 32
        l1 = jnp.asarray(rng.integers(0, 2**32, nwords, dtype=np.uint32))
        valid = jnp.asarray(
            rng.integers(0, 2**32, nwords, dtype=np.uint32)
        )
        return [a, b], l1, valid, b.vb + 64

    return _memo("rowmin_case", build)


def _spec_rowmin_tournament() -> KernelCase:
    from ..ops import relay_pallas as RP
    from ..ops.relay import rowmin_ranks

    classes, l1, valid, vr = _rowmin_case()
    if not any(RP.rowmin_class_ok(cs) for cs in classes):
        raise SkipProgram("no fused-eligible class at lint scale")
    return KernelCase(
        run=lambda: RP.rowmin_ranks_pallas(
            l1, valid, classes, vr, interpret=True
        ),
        twin=lambda: rowmin_ranks(l1, valid, classes, vr),
    )


def _update_case():
    def build():
        import numpy as np

        import jax.numpy as jnp

        from ..ops.relay import PackedRelayState

        # vr a multiple of 32 (the fwords contract) but NOT of the
        # kernel's 4096 alignment — the sentinel-padded tail path runs.
        vr = 4992
        rng = np.random.default_rng(23)
        packed = np.full(vr, 0xFFFFFFFF, np.uint32)
        packed[rng.integers(0, vr, 800)] = rng.integers(
            0, 1 << 26, 800, dtype=np.uint32
        )
        cand = np.full(vr, 0xFFFFFFFF, np.uint32)
        cand[rng.integers(0, vr, 900)] = rng.integers(
            0, 1 << 26, 900, dtype=np.uint32
        )
        state = PackedRelayState(
            jnp.asarray(packed), jnp.zeros(vr // 32, jnp.uint32),
            jnp.int32(2), jnp.bool_(True),
        )
        return state, jnp.asarray(cand)

    return _memo("update_case", build)


def _spec_update_packed() -> KernelCase:
    from ..ops import relay_pallas as RP
    from ..ops.relay import apply_relay_candidates_packed

    state, cand = _update_case()
    return KernelCase(
        run=lambda: RP.apply_relay_candidates_packed_pallas(
            state, cand, interpret=True
        ),
        twin=lambda: apply_relay_candidates_packed(state, cand),
    )


def _mxu_case():
    """Deterministic lint-scale MXU expansion fixture: a small scrambled-
    key tile layout (host oracle builder) whose geometry is deliberately
    unaligned — rows not a multiple of 128, cols under one superblock —
    so the padding conventions (inert tiles, zero frontier pad block,
    sentinel key rows) all execute."""
    def build():
        import numpy as np

        import jax.numpy as jnp

        from ..graph.adj_tiles import build_adj_tiles_host, keys_from_new2old

        rng = np.random.default_rng(41)
        rows, cols, e = 1376, 800, 4000
        src = rng.integers(0, rows, e)
        dst = rng.integers(0, cols, e)
        keys2d = keys_from_new2old(
            rng.permutation(rows).astype(np.int64), rows
        )
        at = build_adj_tiles_host(
            src, dst, rows=rows, cols=cols, keys2d=keys2d
        )
        fw = rng.integers(0, 2**32, at.rtp // 32, dtype=np.uint32)
        # One guaranteed-empty frontier row block: the early-out branch
        # must execute (and the twin must agree it contributes nothing).
        fw[0:4] = 0
        return at, jnp.asarray(fw[: rows // 32 + (1 if rows % 32 else 0)])

    return _memo("mxu_case", build)


def _spec_expand_mxu() -> KernelCase:
    from ..graph.adj_tiles import TILE
    from ..ops import relay_mxu as RM

    at, fw = _mxu_case()
    ops = RM.mxu_device_operands(at)
    windows = []
    ntp = at.ntp
    rb_limit = at.keys2d.shape[0]
    for t in range(ntp):
        windows.append(Window(f"mxu:tile{t}", t, 1, ntp))
        windows.append(Window(f"mxu:fblk{t}", t, 1, ntp))
        windows.append(Window(
            f"mxu:keys{t}", int(at.row_idx[t]), 1, rb_limit
        ))
    return KernelCase(
        run=lambda: RM.expand_frontier_mxu(
            fw, ops, rows=at.rows, cols=at.cols, rtp=at.rtp, vtp=at.vtp,
            interpret=True,
        ),
        twin=lambda: RM.expand_frontier_mxu_xla(
            fw, ops, rows=at.rows, cols=at.cols, rtp=at.rtp, vtp=at.vtp
        ),
        windows=windows,
        mxu=True,  # the PAL002 128x128 contract — first real consumer
    )


def _make_spec(name, sites, build, path=None):
    spec = KernelSpec(
        name=name, path=path or _PAL_PATH, sites=sites, build=build
    )

    def builder():
        return spec

    builder.sites = sites  # registry_findings reads coverage statically
    builder.spec = spec
    return builder


#: name -> spec builder.  Order is the report order.  Together the specs'
#: ``sites`` must equal :func:`discover_pallas_sites` — set-equality
#: pinned by :func:`registry_findings` and tier-1.
KERNEL_SPECS = {
    "benes.word_tile_major": _make_spec(
        "benes.word_tile_major",
        (f"{_PAL_PATH}::_run_local_tile_major", f"{_PAL_PATH}::_run_pass"),
        _spec_benes_word_tile_major,
    ),
    "benes.word_lane_compact": _make_spec(
        "benes.word_lane_compact",
        (f"{_PAL_PATH}::_run_pass",),
        _spec_benes_word_lane_compact,
    ),
    "benes.elem_passes": _make_spec(
        "benes.elem_passes",
        (f"{_PAL_PATH}::_run_elem_pass",),
        _spec_benes_elem,
    ),
    "rowmin.tournament": _make_spec(
        "rowmin.tournament",
        (f"{_PAL_PATH}::_class_tournament_call",),
        _spec_rowmin_tournament,
    ),
    "update.packed_words": _make_spec(
        "update.packed_words",
        (f"{_PAL_PATH}::apply_relay_candidates_packed_pallas",),
        _spec_update_packed,
    ),
    # The MXU expansion arm (ISSUE 15): mxu=True — the first real
    # consumer of the PAL002 128x128 MXU block contract; PAL005 pins the
    # kernel byte-identical to its XLA twin (the raw-bytes oracle was
    # built for exactly this arm).
    "expand.frontier_mxu": _make_spec(
        "expand.frontier_mxu",
        (_MXU_PATH + "::expand_frontier_mxu",),
        _spec_expand_mxu,
        path=_MXU_PATH,
    ),
}


# --------------------------------------------------------------------------
# Content-addressed result cache + the repo entry point.
# --------------------------------------------------------------------------

def default_cache_dir(root: str | None = None) -> str:
    env = knobs.raw("BFS_TPU_PAL_CACHE") or ""
    if env:
        return env
    return os.path.join(root or repo_root(), ".bench_cache", "pal")


def _cache_key(root: str) -> str:
    import jax

    h = hashlib.blake2b(digest_size=16)
    h.update(_source_fingerprint(root).encode())
    h.update(jax.__version__.encode())
    h.update(jax.default_backend().encode())
    h.update(str(len(jax.devices())).encode())
    h.update(str(PAL_VERSION).encode())
    h.update(",".join(sorted(KERNEL_SPECS)).encode())
    for env in _PAL_FLAVOR_ENV:
        h.update(f"{env}={os.environ.get(env, '')};".encode())
    # SkipProgram results are cached, and the Beneš specs skip on a
    # NON-.py input (_source_fingerprint hashes only package sources):
    # building the native router later must miss the cache, or the
    # skipped verdict replays forever.
    try:
        from ..graph import benes

        h.update(f"native={int(benes.native_available())}".encode())
    except Exception:
        h.update(b"native=?")
    return h.hexdigest()


def _finding_to_dict(f: Finding) -> dict:
    return {
        "rule": f.rule, "path": f.path, "line": f.line, "col": f.col,
        "message": f.message, "snippet": f.snippet,
    }


def analyze_pallas(
    specs: dict | None = None,
    *,
    use_cache: bool = True,
    cache_dir: str | None = None,
    root: str | None = None,
) -> tuple[list, dict]:
    """Run the Pallas pass.  Returns ``(findings, meta)``; ``meta``
    records cache disposition, skipped kernels and per-kernel VMEM
    bytes.  ``specs`` overrides the registry (tests feed fixtures);
    custom specs are never cached and skip the repo-wide site pin —
    only the canonical registry proves coverage."""
    _ensure_jax_env()
    root = root or repo_root()
    custom = specs is not None
    specs = specs if custom else KERNEL_SPECS
    meta: dict = {
        "cache": "off" if (custom or not use_cache) else "miss",
        "kernels": [], "skipped": {}, "vmem_bytes": {},
    }

    cache_path = None
    if not custom and use_cache:
        key = _cache_key(root)
        cache_path = os.path.join(
            cache_dir or default_cache_dir(root), f"pal_{key}.json"
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
    if not custom:
        findings.extend(registry_findings(specs, root))
    for name, build in specs.items():
        try:
            spec = build()
            result = analyze_kernel(spec)
        except SkipProgram as exc:
            meta["skipped"][name] = str(exc)
            continue
        except Exception as exc:
            findings.append(Finding(
                rule="PAL000", path="bfs_tpu/analysis/pallas.py",
                line=0, col=0,
                message=f"[{name}] spec builder failed: "
                        f"{type(exc).__name__}: {exc}",
                snippet=f"pal:{name}:builder",
            ))
            continue
        meta["kernels"].append(name)
        vmem = getattr(spec, "_vmem_bytes", None)
        if vmem is not None:
            meta["vmem_bytes"][name] = vmem
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
