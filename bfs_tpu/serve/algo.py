"""Registry-resident semiring algorithms (ISSUE 16).

The serve layer's BFS path uploads a graph's device operands once per
(graph, engine) epoch and reuses them for every query; the semiring
algorithms ride the same residency: :func:`registry_sssp` and
:func:`registry_cc` pin the current epoch (so a concurrent hot-swap or
LRU eviction cannot retire the operands mid-traversal), acquire the
push/pull operands through :meth:`GraphRegistry.acquire_for`, and run
the fused algo programs against them — no per-call H2B upload, and the
registry's HBM budget governs the algorithms exactly as it governs BFS.

SSSP's per-graph operands (the endpoint-hash weights
:func:`bfs_tpu.algo.substrate.edge_weights`, ``dst_ends`` and, where
the push arm runs, the out-row view) are built once from the resident
edge endpoints and held as an entry of their own under the same budget
(:meth:`GraphRegistry.acquire_sssp`).  Registry SSSP refuses a graph
that stores weights of its own.
"""

from __future__ import annotations

from ..algo.cc import CcResult, cc_device, cc_device_pull
from ..algo.sssp import (
    DEFAULT_MAX_WEIGHT,
    SsspResult,
    sssp_device,
    uses_packed16,
)
from .registry import GraphRegistry

__all__ = ["registry_sssp", "registry_cc"]


def _num_vertices(registry: GraphRegistry, rec, engine: str) -> int:
    # acquire_for has already built+memoized the layout; both the
    # DeviceGraph (push) and PullGraph (pull) carry the real unpadded V.
    return int(registry._layout_for(rec, engine).num_vertices)


def registry_sssp(
    registry: GraphRegistry,
    name: str,
    source: int = 0,
    *,
    max_weight: int = DEFAULT_MAX_WEIGHT,
    packed: bool | None = None,
    **kwargs,
) -> SsspResult:
    """Weighted SSSP on a registered graph's resident push operands and
    its resident SSSP operands.  ``kwargs`` pass through to
    :func:`bfs_tpu.algo.sssp.sssp_device` (delta / max_rounds)."""
    rec = registry.pin(name)
    try:
        if rec.graph is not None and rec.graph.weight is not None:
            raise ValueError(
                f"graph {name!r} stores its weights; registry SSSP runs on "
                "the endpoint hash (bfs_tpu.algo.sssp.sssp takes them)")
        src_dev, dst_dev = registry.acquire_for(rec, "push")
        v = _num_vertices(registry, rec, "push")
        push = not uses_packed16(v, floating=False, packed=packed)
        operands = registry.acquire_sssp(rec, max_weight=max_weight,
                                         push=push)
        return sssp_device(
            src_dev, dst_dev, v, source, max_weight=max_weight,
            packed=packed, operands=operands, **kwargs,
        )
    finally:
        registry.unpin(rec)


def registry_cc(
    registry: GraphRegistry,
    name: str,
    *,
    engine: str = "push",
    max_rounds: int | None = None,
) -> CcResult:
    """Connected components on a registered graph's resident operands
    (``engine`` = push | pull; both reach the same label fixpoint)."""
    if engine not in ("push", "pull"):
        raise ValueError(
            f"unknown engine {engine!r}; registry CC runs 'push' or 'pull'"
        )
    rec = registry.pin(name)
    try:
        operands = registry.acquire_for(rec, engine)
        v = _num_vertices(registry, rec, engine)
        if engine == "pull":
            ell0, folds = operands
            return cc_device_pull(ell0, folds, v, max_rounds=max_rounds)
        src_dev, dst_dev = operands
        return cc_device(src_dev, dst_dev, v, max_rounds=max_rounds)
    finally:
        registry.unpin(rec)
