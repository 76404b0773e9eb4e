"""Landmark distance-label oracle tier (ISSUE 20): stop paying a full
traversal per point query.

The serve path answered every ``dist(u, v)`` with a full level-synchronous
traversal from ``u`` — the wrong shape for heavy read traffic (the paper's
own workload).  The bit-packed multi-source machinery
(:mod:`bfs_tpu.models.multisource`) makes a few-hundred-root sweep cheap,
so at ``register()`` time we precompute K landmark BFS forests once and
answer point queries from the resulting **distance labels** in one tiny
batched gather+min program:

* **schema** — ``dist: uint16[K, V]`` (0xFFFF = unreachable sentinel) is
  the device-resident half; ``parent: int32[K, V]`` + ``landmarks:
  int32[K]`` stay on host for path reconstruction.  Every graph the
  framework builds is undirected (``Graph.from_undirected_edges``), so one
  forward label set serves both query directions.
* **tightness certificate** — for undirected graphs the labels bound the
  true distance both ways: ``upper = min_k(d[k,u] + d[k,v])`` and
  ``lower = max_k |d[k,u] - d[k,v]|`` (the ALT bound).  When
  ``upper == max(lower, 1)`` (or ``u == v``) the bound is PROVABLY exact
  and the label answer ships; the walk u->landmark->v of that length is a
  shortest path, which is what :meth:`LabelOracle.path` reconstructs.  A
  landmark reaching exactly one of ``u, v`` certifies the pair
  disconnected (exact ``INF_DIST``).  Anything else falls back to the
  exact traversal — labels may only ever make answers FASTER, never
  wrong.
* **content addressing** — the index is a pure function of (graph
  content, K, label code version), cached as a sidecar bundle next to the
  layout bundle (:func:`bfs_tpu.cache.layout.load_or_build_labels`) and
  budget-gated like the serve registry (``BFS_TPU_LABELS_GB``).
* **resilience** — the K-root sweep is chunked and each finished chunk is
  a durable epoch in the superstep-checkpoint store, so a killed
  precompute resumes at the last chunk boundary bit-identically.  Built
  rows are sample-verified with the :class:`DeviceChecker` before the
  index is trusted.
"""

from __future__ import annotations

import hashlib
import logging
import os
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import knobs
from ..analysis.runtime import traced
from ..graph.csr import Graph, INF_DIST, NO_PARENT

logger = logging.getLogger(__name__)

#: Bump on any change to the label math or array schema — part of the
#: sidecar bundle key, so old bundles simply miss.
LABELS_VERSION = 1

#: uint16 unreachable sentinel inside the device-resident label rows.
LABEL_INF = 0xFFFF

#: Landmark roots swept per multi-source chunk (and per checkpoint epoch);
#: 64 matches the packed fused-word batch the engine digests best.
DEFAULT_CHUNK = 64


class LabelBudgetError(ValueError):
    """The label index does not fit ``BFS_TPU_LABELS_GB`` — the server
    drops to exact-only serving rather than evicting engine arrays."""


@dataclass(frozen=True)
class LabelIndex:
    """One graph's landmark distance labels (host-side arrays)."""

    landmarks: np.ndarray  # int32[K]
    dist: np.ndarray       # uint16[K, V], LABEL_INF = unreachable
    parent: np.ndarray     # int32[K, V], NO_PARENT = unreached
    num_vertices: int

    @property
    def k(self) -> int:
        return int(self.landmarks.shape[0])

    @property
    def device_bytes(self) -> int:
        """Bytes the resident half (dist rows) costs on device."""
        return int(self.dist.nbytes)

    @property
    def nbytes(self) -> int:
        return int(
            self.dist.nbytes + self.parent.nbytes + self.landmarks.nbytes
        )


def labels_to_arrays(idx: LabelIndex) -> dict:
    return {
        "dims": np.asarray(
            [LABELS_VERSION, idx.k, idx.num_vertices], dtype=np.int64
        ),
        "landmarks": np.asarray(idx.landmarks, dtype=np.int32),
        "dist": np.asarray(idx.dist, dtype=np.uint16),
        "parent": np.asarray(idx.parent, dtype=np.int32),
    }


def labels_from_arrays(arrays: dict) -> LabelIndex:
    dims = np.asarray(arrays["dims"])
    if int(dims[0]) != LABELS_VERSION:
        raise ValueError(
            f"label bundle version {int(dims[0])} != {LABELS_VERSION}"
        )
    return LabelIndex(
        landmarks=np.asarray(arrays["landmarks"]),
        dist=np.asarray(arrays["dist"]),
        parent=np.asarray(arrays["parent"]),
        num_vertices=int(dims[2]),
    )


# ------------------------------------------------------------- sampling --

def sample_landmarks(graph: Graph, k: int) -> np.ndarray:
    """K degree-weighted landmark roots, int32, deterministic per graph
    content (seeded from the same blake2b the cache key uses — a rebuild
    of the same graph always picks the same landmarks, so the sidecar key
    needs only (graph, K)).  High-degree hubs sit on many shortest paths,
    which is what makes the tightness certificate fire; zero-degree
    vertices are never useful landmarks and are excluded.  K is clamped
    to the number of usable roots."""
    from ..cache.layout import graph_content_hash

    if k < 1:
        raise ValueError(f"need k >= 1 landmarks (got {k})")
    v = int(graph.num_vertices)
    src = np.asarray(graph.src).reshape(-1)
    src = src[(src >= 0) & (src < v)]  # drop DeviceGraph sentinel padding
    deg = np.bincount(src, minlength=v).astype(np.float64)
    usable = np.flatnonzero(deg > 0)
    if usable.size == 0:
        # Edgeless graph: every pair is trivially u==v or disconnected;
        # any vertex works as the single landmark.
        return np.zeros((min(k, graph.num_vertices),), dtype=np.int32)
    seed = int.from_bytes(
        hashlib.blake2b(
            graph_content_hash(graph).encode(), digest_size=8
        ).digest(),
        "big",
    )
    rng = np.random.default_rng(seed)
    k_eff = min(int(k), int(usable.size))
    p = deg[usable] / deg[usable].sum()
    picked = rng.choice(usable, size=k_eff, replace=False, p=p)
    return np.sort(picked).astype(np.int32)


# ---------------------------------------------------------------- build --

def build_label_index(
    graph: Graph,
    k: int,
    *,
    engine: str = "pull",
    layout=None,
    chunk: int = DEFAULT_CHUNK,
    ckpt_dir: str | os.PathLike | None = None,
    verify_rows: int = 2,
) -> LabelIndex:
    """Sweep K landmark roots through the multi-source engine and pack the
    forests into a :class:`LabelIndex`.  ``layout`` is a prebuilt
    ``engine`` layout of ``graph`` (the serve registry's); None builds one.

    The sweep runs in ``chunk``-root slices; when superstep checkpointing
    is on (``BFS_TPU_CKPT``), every finished slice is saved as a durable
    epoch keyed on (graph content, K, engine, chunk) — a killed build
    resumes at the last chunk boundary and the finished index is
    bit-identical to an uninterrupted one (the multi-source engine is
    deterministic).  ``verify_rows`` sampled forests are re-checked with
    the :class:`DeviceChecker` before the index is returned."""
    from ..models.multisource import bfs_multi
    from ..resilience.superstep_ckpt import SuperstepCheckpointer

    landmarks = sample_landmarks(graph, k)
    kk, v = int(landmarks.shape[0]), int(graph.num_vertices)
    chunk = max(1, int(chunk))
    dist16 = np.full((kk, v), LABEL_INF, dtype=np.uint16)
    parent = np.full((kk, v), NO_PARENT, dtype=np.int32)

    if ckpt_dir is None:
        from ..config import cache_root

        ckpt_dir = os.path.join(cache_root(), "ckpt")
    from ..cache.layout import graph_content_hash

    ckpt = SuperstepCheckpointer(
        ckpt_dir,
        {
            "kind": "labels",
            "graph": graph_content_hash(graph),
            "k": kk,
            "engine": engine,
            "chunk": chunk,
        },
    )
    start = 0
    if ckpt.enabled:
        found = ckpt.load_latest()
        if found is not None:
            ep, arrays, _ = found
            dist16[:] = np.asarray(arrays["dist"], dtype=np.uint16)
            parent[:] = np.asarray(arrays["parent"], dtype=np.int32)
            start = int(ep)
            logger.info(
                "label precompute resuming at chunk %d/%d",
                start, -(-kk // chunk),
            )

    for ci in range(start, -(-kk // chunk)):
        roots = landmarks[ci * chunk : (ci + 1) * chunk]
        res = bfs_multi(
            graph if layout is None else layout, roots, engine=engine
        )
        d = np.asarray(res.dist)
        reach = d != INF_DIST
        if reach.any() and int(d[reach].max()) >= LABEL_INF:
            raise ValueError(
                f"graph eccentricity {int(d[reach].max())} exceeds the "
                f"uint16 label range; label tier unavailable"
            )
        rows = slice(ci * chunk, ci * chunk + roots.shape[0])
        dist16[rows] = np.where(reach, d, LABEL_INF).astype(np.uint16)
        parent[rows] = np.asarray(res.parent)
        # Chunk boundary = durable epoch = kill point (fault boundary
        # fires inside save_epoch AFTER the write, even in off mode).
        ckpt.save_epoch(ci + 1, {"dist": dist16, "parent": parent})
    if ckpt.enabled:
        ckpt.clear()

    idx = LabelIndex(
        landmarks=landmarks, dist=dist16, parent=parent, num_vertices=v
    )
    _verify_rows(graph, idx, verify_rows)
    return idx


def _verify_rows(graph: Graph, idx: LabelIndex, rows: int) -> None:
    """Sample-verify built forests with the DeviceChecker — the same
    verdict program every serve reply goes through.  A violation means
    the index can never be trusted: raise, do not serve."""
    if rows < 1 or idx.k == 0:
        return
    from ..oracle.device import DeviceChecker

    checker = DeviceChecker.from_graph(graph)
    take = np.linspace(0, idx.k - 1, min(int(rows), idx.k)).astype(int)
    for r in np.unique(take):
        d = idx.dist[r].astype(np.int32)
        d = np.where(idx.dist[r] == LABEL_INF, INF_DIST, d)
        bad = checker.check(
            d, idx.parent[r], np.asarray([idx.landmarks[r]], dtype=np.int32)
        )
        if bad:
            raise ValueError(
                f"label row for landmark {int(idx.landmarks[r])} failed "
                f"device verification: {bad}"
            )


# -------------------------------------------------------- device lookup --

@jax.jit
@traced("labels._label_bounds")
def _label_bounds(dist16, u, v):
    """One batched label lookup: gather both label columns, reduce over
    the landmark axis, emit the distance plus the tightness certificate.

    Returns ``(dist, tight, best_k, upper, lower)`` over the pair batch:
    ``tight`` marks answers that are PROVABLY exact — ``u == v``, the
    sandwich ``upper == max(lower, 1)`` (a walk of length d(u,v) is a
    shortest path, and d >= 1 off-diagonal), or a landmark seeing exactly
    one endpoint (certified disconnected, ``dist == INF_DIST``)."""
    du = dist16[:, u].astype(jnp.int32)  # [K, B]
    dv = dist16[:, v].astype(jnp.int32)
    fu = du != LABEL_INF
    fv = dv != LABEL_INF
    both = fu & fv
    up = jnp.where(both, du + dv, INF_DIST)
    upper = jnp.min(up, axis=0)
    best_k = jnp.argmin(up, axis=0).astype(jnp.int32)
    lower = jnp.max(jnp.where(both, jnp.abs(du - dv), 0), axis=0)
    unreach = jnp.any(fu != fv, axis=0)
    same = u == v
    covered = jnp.any(both, axis=0)
    tight = same | unreach | (
        covered & (upper == jnp.maximum(lower, 1))
    )
    dist = jnp.where(same, 0, jnp.where(unreach, INF_DIST, upper))
    return dist, tight, best_k, upper, lower


# ---------------------------------------------------------------- oracle --

class LabelOracle:
    """Device-resident query object over one :class:`LabelIndex`.

    Holds the uint16 dist rows on device (budget-gated) and the parent
    forest on host; answers batched ``dist``/``path`` point queries in one
    compiled gather+min (:func:`_label_bounds`, registered as
    ``serve.label_lookup`` in the IR program registry)."""

    def __init__(self, index: LabelIndex, *, budget_bytes: int | None = None):
        if budget_bytes is not None and index.device_bytes > budget_bytes:
            raise LabelBudgetError(
                f"label index is {index.device_bytes >> 20} MB on device, "
                f"over the {budget_bytes >> 20} MB budget "
                f"(BFS_TPU_LABELS_GB)"
            )
        self.index = index
        self._dist_dev = jax.device_put(np.asarray(index.dist))
        self.queries = 0
        self.tight_hits = 0

    @property
    def k(self) -> int:
        return self.index.k

    @property
    def device_bytes(self) -> int:
        return self.index.device_bytes

    def bounds(self, u, v):
        """``(dist, tight, best_k, upper, lower)`` as host numpy arrays
        over the pair batch — one device round trip."""
        u = np.atleast_1d(np.asarray(u, dtype=np.int32))
        v = np.atleast_1d(np.asarray(v, dtype=np.int32))
        if u.shape != v.shape:
            raise ValueError("u and v batches must have equal shape")
        nv = self.index.num_vertices
        if u.size and (
            int(min(u.min(), v.min())) < 0
            or int(max(u.max(), v.max())) >= nv
        ):
            raise ValueError(f"query vertex outside [0, {nv})")
        out = jax.device_get(_label_bounds(self._dist_dev, u, v))
        dist, tight, best_k, upper, lower = (np.asarray(a) for a in out)
        self.queries += int(u.size)
        self.tight_hits += int(tight.sum())
        return dist, tight, best_k, upper, lower

    def dist(self, u, v):
        """``(dist, tight, best_k)`` for a pair batch; ``dist`` entries
        are exact wherever ``tight`` holds and an upper bound elsewhere
        (callers MUST fall back on non-tight pairs)."""
        d, tight, best_k, _, _ = self.bounds(u, v)
        return d, tight, best_k

    def dist_one(self, u: int, v: int):
        d, tight, best_k = self.dist([u], [v])
        return int(d[0]), bool(tight[0]), int(best_k[0])

    def path(self, u: int, v: int):
        """An EXACT shortest path ``[u, ..., v]`` when the certificate is
        tight and the pair connected, else None (caller falls back to a
        traversal).  The u->landmark and landmark->v legs come from the
        host parent forest; their concatenation has length
        ``d(k,u) + d(k,v) == d(u,v)``, hence is a shortest path."""
        if u == v:
            return [int(u)]
        d, tight, best_k, _, _ = self.bounds([u], [v])
        if not bool(tight[0]) or int(d[0]) >= INF_DIST:
            return None
        row = self.index.parent[int(best_k[0])]
        lm = int(self.index.landmarks[int(best_k[0])])
        a = self._chain(row, int(u), lm)
        b = self._chain(row, int(v), lm)
        if a is None or b is None:
            return None
        return a + b[::-1][1:]

    def _chain(self, parent_row, start: int, landmark: int):
        chain = [start]
        cur = start
        limit = self.index.num_vertices
        while cur != landmark:
            cur = int(parent_row[cur])
            if cur < 0 or len(chain) > limit:
                return None
            chain.append(cur)
        return chain

    def report(self) -> dict:
        return {
            "k": self.k,
            "device_bytes": self.device_bytes,
            "queries": self.queries,
            "tight_hits": self.tight_hits,
        }


def labels_budget_bytes() -> int:
    """The resident-label budget in bytes (``BFS_TPU_LABELS_GB``)."""
    return int(knobs.get("BFS_TPU_LABELS_GB") * (1 << 30))


def build_label_oracle(
    graph: Graph,
    k: int,
    *,
    cache=None,
    engine: str = "pull",
    layout=None,
    ckpt_dir: str | os.PathLike | None = None,
):
    """``(LabelOracle, info)`` — the server's register-time entry point:
    the sidecar-cached index (:func:`bfs_tpu.cache.layout
    .load_or_build_labels`) wrapped in a budget-gated device oracle.
    Raises :class:`LabelBudgetError` over budget — callers keep serving
    exact-only."""
    from ..cache.layout import load_or_build_labels

    t0 = time.perf_counter()
    idx, info = load_or_build_labels(
        graph, k, cache=cache, engine=engine, layout=layout,
        ckpt_dir=ckpt_dir,
    )
    oracle = LabelOracle(idx, budget_bytes=labels_budget_bytes())
    info = dict(info)
    info["total_seconds"] = time.perf_counter() - t0
    return oracle, info
