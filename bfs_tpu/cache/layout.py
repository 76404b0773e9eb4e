"""Persistent layout-bundle cache: the 434-second build, paid once ever.

Round 5's driver-verified headline was gated on the COLD path, not the
kernel: a fresh process burned 434 s rebuilding the relay layout before a
single superstep ran (VERDICT r5 "missing" #1).  The layout is a pure
function of (graph content, layout parameters, layout code version), so it
is a cacheable artifact — this module stores finished layouts
(:class:`~bfs_tpu.graph.relay.RelayGraph` permutation masks / ELL folds /
sparse adjacency / class metadata) as content-addressed on-disk bundles:

  * **bundle** = one directory ``<root>/<key>/`` holding ``meta.json`` plus
    one ``.npy`` file per array field.  Large arrays load back as
    ``np.memmap`` views, so a warm load is directory-walk + header-read
    cheap — the mask gigabytes stream lazily when the engine ships them to
    the device (which it was going to do anyway).
  * **key** = ``{kind}_{layout params}_s{STORE_VERSION}_{graph hash}``
    where the graph hash is a blake2b over ``(V, E, src, dst)`` — a code
    bump (LAYOUT_VERSION / STORE_VERSION), a parameter change, or a
    different graph can never alias a stale bundle.
  * **integrity** — every field records dtype/shape and a head+tail
    fingerprint in ``meta.json``; a failed check (truncated write, manual
    tampering) drops the bundle and reports a miss, so the worst case is a
    rebuild, never a wrong layout.
  * **atomicity** — bundles are written to a ``.tmp.<pid>`` sibling and
    renamed into place; concurrent builders race benignly (first rename
    wins, the loser discards its copy).
  * **tags** — optional human-readable aliases (``tags/<name>.json`` ->
    key) so callers that know their graph only by config (the bench's
    scale-fallback estimator, before the graph is even generated) can
    probe warmth without hashing anything.

The serializers live next to the dataclasses they flatten
(:func:`~bfs_tpu.graph.relay.relay_to_arrays`,
:func:`~bfs_tpu.graph.ell.pull_to_arrays`); this module only owns the disk
format.  Hit/miss counts feed :func:`bfs_tpu.utils.metrics.bump_artifact`.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import shutil
import time
from typing import Any

import numpy as np

from .. import knobs
from ..utils.metrics import bump_artifact

logger = logging.getLogger(__name__)

#: Bump on any change to the bundle disk format (meta schema, fingerprint
#: rule, file layout).  Part of every key, so old bundles simply miss.
STORE_VERSION = 1

#: Elements hashed from each end of an array for the integrity fingerprint.
#: Full-array hashing would re-read gigabytes and defeat the memmap load;
#: head+tail+length catches the real corruption modes (truncation, partial
#: writes, wrong file) without touching the middle.
_FPR_ELEMS = 16384

#: Arrays at or under this byte size load eagerly (a 0-d scalar or a class
#: table is cheaper to read than to memmap); everything larger memmaps.
_MMAP_MIN_BYTES = 1 << 23


def default_root() -> str:
    from ..config import layout_cache_dir

    return layout_cache_dir()


def graph_content_hash(graph) -> str:
    """blake2b-128 over ``(num_vertices, E, src bytes, dst bytes)``.

    Accepts anything with ``num_vertices``/``src``/``dst`` (host
    :class:`~bfs_tpu.graph.csr.Graph` or a padded
    :class:`~bfs_tpu.graph.csr.DeviceGraph` — padding bytes hash too, which
    is conservative: a padding change rebuilds rather than aliases).
    Memoized on the object; ~1-2 s for the 1.6 GB s24 edge arrays.
    """
    cached = getattr(graph, "_content_hash", None)
    if cached is not None:
        return cached
    h = hashlib.blake2b(digest_size=16)
    src = np.ascontiguousarray(np.asarray(graph.src).reshape(-1))
    dst = np.ascontiguousarray(np.asarray(graph.dst).reshape(-1))
    h.update(np.int64(graph.num_vertices).tobytes())
    h.update(np.int64(src.shape[0]).tobytes())
    h.update(str(src.dtype).encode())
    h.update(memoryview(src))
    h.update(memoryview(dst))
    digest = h.hexdigest()
    try:
        object.__setattr__(graph, "_content_hash", digest)
    except (AttributeError, TypeError):
        pass
    return digest


def relay_key(graph) -> str:
    from ..graph.relay import COMPACT_MIN_D, LAYOUT_VERSION

    return (
        f"relay_v{LAYOUT_VERSION}c{COMPACT_MIN_D}_s{STORE_VERSION}"
        f"_{graph_content_hash(graph)}"
    )


def pull_key(graph, k: int, row_multiple: int) -> str:
    return (
        f"pull_k{k}r{row_multiple}_s{STORE_VERSION}"
        f"_{graph_content_hash(graph)}"
    )


def _fingerprint(arr: np.ndarray) -> str:
    """Cheap integrity fingerprint: dtype + shape + head/tail sample.
    Works on memmaps without faulting in the full array."""
    arr = np.asarray(arr)
    h = hashlib.blake2b(digest_size=8)
    h.update(str(arr.dtype).encode())
    h.update(repr(tuple(arr.shape)).encode())
    flat = arr.reshape(-1)
    take = min(int(flat.shape[0]), _FPR_ELEMS)
    h.update(np.ascontiguousarray(flat[:take]).tobytes())
    h.update(np.ascontiguousarray(flat[flat.shape[0] - take :]).tobytes())
    return h.hexdigest()


class LayoutCache:
    """Content-addressed bundle store under one root directory."""

    def __init__(self, root: str | None = None):
        self.root = root or default_root()

    # ------------------------------------------------------------ bundles --
    def _dir(self, key: str) -> str:
        return os.path.join(self.root, key)

    def has(self, key: str) -> bool:
        return os.path.isfile(os.path.join(self._dir(key), "meta.json"))

    def save(
        self,
        key: str,
        arrays: dict[str, np.ndarray],
        meta: dict[str, Any] | None = None,
        *,
        tag: str | None = None,
    ) -> None:
        """Write a bundle atomically; ``meta`` is free-form JSON (build
        seconds, provenance).  A concurrent save of the same key races
        benignly — the first finished rename wins."""
        final = self._dir(key)
        tmp = f"{final}.tmp.{os.getpid()}"
        os.makedirs(tmp, exist_ok=True)
        try:
            fields = {}
            for name, arr in arrays.items():
                arr = np.asarray(arr)
                np.save(os.path.join(tmp, f"{name}.npy"), arr)
                fields[name] = {
                    "dtype": str(arr.dtype),
                    "shape": list(arr.shape),
                    "fingerprint": _fingerprint(arr),
                }
            doc = {
                "key": key,
                "store_version": STORE_VERSION,
                "created": time.time(),
                "fields": fields,
                "meta": meta or {},
            }
            with open(os.path.join(tmp, "meta.json"), "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            if os.path.isdir(final):
                shutil.rmtree(tmp, ignore_errors=True)  # lost the race
            else:
                try:
                    os.rename(tmp, final)
                except OSError:
                    shutil.rmtree(tmp, ignore_errors=True)
        except Exception:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        if tag:
            self.tag(tag, key)

    def load(self, key: str, *, mmap: bool = True):
        """``(meta_doc, arrays)`` for a valid bundle, else None.

        Every field is checked against its recorded dtype/shape/fingerprint;
        any mismatch (or a stale key / store version) drops the bundle so
        the caller rebuilds — corruption can only cost time, not
        correctness."""
        d = self._dir(key)
        meta_path = os.path.join(d, "meta.json")
        if not os.path.isfile(meta_path):
            return None
        try:
            with open(meta_path) as f:
                doc = json.load(f)
            if doc.get("key") != key or doc.get("store_version") != STORE_VERSION:
                raise ValueError("bundle key/store-version mismatch")
            arrays = {}
            for name, spec in doc["fields"].items():
                nbytes = int(
                    np.dtype(spec["dtype"]).itemsize
                    * max(int(np.prod(spec["shape"] or [1])), 1)
                )
                arr = np.load(
                    os.path.join(d, f"{name}.npy"),
                    mmap_mode="r" if (mmap and nbytes > _MMAP_MIN_BYTES) else None,
                )
                if (
                    str(arr.dtype) != spec["dtype"]
                    or list(arr.shape) != spec["shape"]
                    or _fingerprint(arr) != spec["fingerprint"]
                ):
                    raise ValueError(f"integrity check failed on field {name!r}")
                arrays[name] = arr
            return doc, arrays
        except (OSError, MemoryError) as exc:
            # Environmental failure (fd pressure, remote-FS hiccup, OOM):
            # report a miss but do NOT delete — the bundle may be intact
            # and a 434 s artifact must not die to a transient error.
            logger.warning("layout bundle %s unreadable (kept): %s", key, exc)
            return None
        except Exception as exc:
            logger.warning("dropping corrupt/stale layout bundle %s: %s", key, exc)
            self.invalidate(key)
            return None

    def invalidate(self, key: str) -> None:
        shutil.rmtree(self._dir(key), ignore_errors=True)

    # --------------------------------------------------------------- tags --
    def _tag_path(self, tag: str) -> str:
        safe = "".join(c if (c.isalnum() or c in "._-") else "_" for c in tag)
        return os.path.join(self.root, "tags", f"{safe}.json")

    def tag(self, tag: str, key: str) -> None:
        """Alias ``tag`` -> ``key`` (atomic single-file write)."""
        path = self._tag_path(tag)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"key": key}, f)
        os.replace(tmp, path)

    def resolve_tag(self, tag: str) -> str | None:
        """The key a tag points at, iff that bundle exists — the
        hash-free warmth probe the bench estimator uses before the graph
        is generated."""
        try:
            with open(self._tag_path(tag)) as f:
                key = json.load(f)["key"]
        except (OSError, ValueError, KeyError):
            return None
        return key if self.has(key) else None


# ---------------------------------------------------------------------------
# High-level load-or-build: the one call sites use.
# ---------------------------------------------------------------------------

def _load_or_build(graph, *, cache, tag, kind, key_fn, build_fn, to_arrays,
                   from_arrays, build_meta: dict | None = None,
                   prepare_build=None):
    """Shared load-or-build skeleton; the ``info`` dict contract lives in
    ONE place: ``cache`` ("hit"/"miss"/"disabled"), ``key``,
    ``load_seconds`` (hit) or ``save_seconds`` (miss), and
    ``build_seconds`` — on a hit the COLD build time recorded when the
    bundle was written, so every warm run can report its warm-vs-cold
    speedup.  ``build_meta`` is builder provenance (flavor, per-stage
    seconds): recorded in the bundle on a miss, replayed from the bundle's
    ``meta`` on a hit, and merged into the returned info either way.
    ``prepare_build`` runs only when a build is actually imminent, OUTSIDE
    the timed window — once-per-process costs (module imports, worker-pool
    start) stay off both the warm path and the build clock."""
    from ..obs.spans import span as obs_span

    build_meta = build_meta if build_meta is not None else {}
    if cache is None:
        if prepare_build is not None:
            prepare_build()
        t0 = time.perf_counter()
        with obs_span("layout.build", kind=kind):
            obj = build_fn()
        return obj, {
            "cache": "disabled",
            "build_seconds": time.perf_counter() - t0,
            **build_meta,
        }
    t0 = time.perf_counter()
    key = key_fn()
    with obs_span("layout.bundle_load", kind=kind):
        loaded = cache.load(key)
    if loaded is not None:
        doc, arrays = loaded
        obj = from_arrays(arrays)
        bump_artifact("layout_cache_hits")
        if tag:
            cache.tag(tag, key)
        meta = doc["meta"]
        return obj, {
            "cache": "hit",
            "key": key,
            "load_seconds": time.perf_counter() - t0,
            "build_seconds": float(meta.get("build_seconds", -1.0)),
            # provenance of the COLD build that wrote this bundle
            **{
                k: meta[k]
                for k in ("builder", "build_stages")
                if k in meta
            },
        }
    bump_artifact("layout_cache_misses")
    if prepare_build is not None:
        prepare_build()
    t1 = time.perf_counter()
    with obs_span("layout.build", kind=kind):
        obj = build_fn()
    build_seconds = time.perf_counter() - t1
    t2 = time.perf_counter()
    with obs_span("layout.bundle_save", kind=kind):
        cache.save(
            key,
            to_arrays(obj),
            {
                "kind": kind,
                "build_seconds": build_seconds,
                # getattr: sidecar artifacts (adj tiles) size differently
                "num_vertices": int(getattr(obj, "num_vertices", -1)),
                "num_edges": int(getattr(obj, "num_edges", -1)),
                **build_meta,
            },
            tag=tag,
        )
    return obj, {
        "cache": "miss",
        "key": key,
        "build_seconds": build_seconds,
        "save_seconds": time.perf_counter() - t2,
        **build_meta,
    }


def resolve_builder(builder: str | None = None) -> str:
    """Relay builder flavor: explicit arg > ``BFS_TPU_LAYOUT_BUILD`` >
    ``device`` (the first-touch default since ISSUE 10; ``host`` is the
    pinned oracle builder)."""
    builder = builder or knobs.get("BFS_TPU_LAYOUT_BUILD")
    if builder not in ("device", "host"):
        raise ValueError(
            f"unknown layout builder {builder!r}; use device|host"
        )
    return builder


def load_or_build_relay(graph, *, cache: LayoutCache | None = None,
                        tag: str | None = None, builder: str | None = None):
    """``(RelayGraph, info)`` — disk-cached build of the relay layout
    (info contract: :func:`_load_or_build`).

    ``builder`` selects the DEVICE pipeline (graph/relay_device.py — the
    default first-touch path) or the host oracle builder
    (``BFS_TPU_LAYOUT_BUILD=host``); the resulting bundles are
    byte-identical either way (parity-tested), so the flavor never splits
    the content-addressed cache.  A device-build failure falls back to the
    host builder with a logged warning — a build must never be less
    available than it was before the device path existed."""
    from ..graph.relay import build_relay_graph, relay_from_arrays, relay_to_arrays

    builder = resolve_builder(builder)
    stage_times: dict = {}
    build_meta = {"builder": builder, "build_stages": stage_times}
    device_builder: list = []

    def prepare():
        # Import only when a build is imminent (warm hits never pay the
        # module + worker-pool startup), and OUTSIDE the timed window —
        # the host flavor's module is imported long before its build is
        # timed, so the device flavor gets the same treatment.
        if builder == "device" and not device_builder:
            from ..graph.relay_device import build_relay_graph_device

            device_builder.append(build_relay_graph_device)

    def build():
        if builder == "device":
            try:
                return device_builder[0](graph, stage_times=stage_times)
            except Exception as exc:
                logger.warning(
                    "device layout build failed (%r); falling back to the "
                    "host builder", exc,
                )
                stage_times.clear()
                stage_times["fallback"] = repr(exc)
                build_meta["builder"] = "host"  # what actually built it
        return build_relay_graph(graph)

    return _load_or_build(
        graph,
        cache=cache,
        tag=tag,
        kind="relay",
        key_fn=lambda: relay_key(graph),
        build_fn=build,
        to_arrays=relay_to_arrays,
        from_arrays=relay_from_arrays,
        build_meta=build_meta,
        prepare_build=prepare,
    )


def tiles_key(rg) -> str:
    """Content key for the MXU adjacency-tile SIDECAR bundle (ISSUE 15):
    blake2b over the relay layout's relabeled edge structure + relabel
    table — everything the tile builder consumes.  A SIDECAR next to —
    never inside — the relay bundle, so the relay schema (and every
    existing bundle) stays byte-identical."""
    from ..graph.adj_tiles import TILES_VERSION

    h = hashlib.blake2b(digest_size=16)
    for arr in (rg.adj_indptr, rg.adj_dst, rg.new2old):
        a = np.ascontiguousarray(np.asarray(arr))
        h.update(str(a.dtype).encode())
        h.update(memoryview(a))
    h.update(np.int64(rg.vr).tobytes())
    return f"adjtiles_v{TILES_VERSION}_s{STORE_VERSION}_{h.hexdigest()}"


def load_or_build_tiles(rg, *, cache: LayoutCache | None = None,
                        builder: str | None = None,
                        budget_bytes: int | None = None):
    """``(AdjTiles, info)`` — the MXU arm's tiled adjacency, disk-cached
    as a sidecar bundle (info contract: :func:`_load_or_build`).  The
    host builder is the pinned oracle; the device arm
    (``BFS_TPU_TILES_BUILD``, default device) is bit-identical and falls
    back to host on failure.  ``BFS_TPU_TILES_CACHE=1`` enables the
    default on-disk cache when the caller passes none (engine inits stay
    build-only by default — fixture-scale tiles build in milliseconds)."""
    from ..graph.adj_tiles import (
        build_adj_tiles_from_relay,
        resolve_tiles_builder,
        tiles_from_arrays,
        tiles_to_arrays,
    )

    if cache is None and knobs.get("BFS_TPU_TILES_CACHE"):
        cache = LayoutCache()
    builder = resolve_tiles_builder(builder)
    at, info = _load_or_build(
        rg,
        cache=cache,
        tag=None,
        kind="adj_tiles",
        key_fn=lambda: tiles_key(rg),
        build_fn=lambda: build_adj_tiles_from_relay(
            rg, builder, budget_bytes
        ),
        to_arrays=tiles_to_arrays,
        from_arrays=tiles_from_arrays,
        build_meta={"builder": builder},
    )
    # The budget must gate WARM HITS too: the key does not include the
    # budget knob, so a bundle built under a looser BFS_TPU_MXU_TILE_GB
    # would otherwise ship right past a tightened one.
    if budget_bytes is not None and at.nbytes > budget_bytes:
        raise ValueError(
            f"cached adjacency tile layout is {at.nbytes >> 20} MB, over "
            f"the {budget_bytes >> 20} MB budget (BFS_TPU_MXU_TILE_GB)"
        )
    return at, info


def verify_tiles_bundle(rg, *, cache: LayoutCache | None = None) -> dict:
    """Integrity report of the adj-tiles sidecar bundle for ``rg``
    WITHOUT building on a miss (the cache_warm ``--tiles`` check): loads
    the bundle — every array fingerprint-checked by :meth:`LayoutCache.load`,
    a corrupt field surfaces as ``absent`` — then validates the geometry
    invariants the streamed host store (stream/store.py) leans on:
    version/shape agreement with the relay graph, a monotone
    ``sb_indptr`` closing at ``nt``, and every real tile's row/column ids
    inside the padded spaces.  Returns a JSON-ready dict; never raises on
    a bad bundle."""
    from ..graph.adj_tiles import (
        SB_VERTS,
        TILE,
        TILES_VERSION,
        tiles_from_arrays,
    )

    cache = cache if cache is not None else LayoutCache()
    key = tiles_key(rg)
    loaded = cache.load(key)
    if loaded is None:
        return {"key": key, "ok": False, "status": "absent"}
    _doc, arrays = loaded
    try:
        at = tiles_from_arrays(arrays)
    except Exception as exc:  # stale dims row / shape drift
        return {"key": key, "ok": False, "status": f"unreadable: {exc}"}
    problems = []
    if int(arrays["dims"][0]) != TILES_VERSION:
        problems.append(
            f"tiles version {int(arrays['dims'][0])} != {TILES_VERSION}"
        )
    if at.rows != rg.vr:
        problems.append(f"rows {at.rows} != relay vr {rg.vr}")
    sb = np.asarray(at.sb_indptr)
    if not (np.all(np.diff(sb) >= 0) and int(sb[0]) == 0
            and int(sb[-1]) == at.nt):
        problems.append("sb_indptr not a monotone span table closing at nt")
    nt = at.nt
    if nt:
        if int(np.asarray(at.row_idx[:nt]).max()) >= at.rtp // TILE:
            problems.append("real tile row_idx outside the padded row space")
        if int(np.asarray(at.col_id[:nt]).max()) >= at.vtp // TILE:
            problems.append("real tile col_id outside the padded col space")
    return {
        "key": key,
        "ok": not problems,
        "status": "ok" if not problems else "; ".join(problems),
        "num_tiles": int(at.nt),
        "num_superblocks": int(at.vtp // SB_VERTS),
        "tile_bytes": int(at.nbytes),
    }


def labels_key(graph, k: int) -> str:
    """Content key for the landmark distance-label SIDECAR bundle
    (ISSUE 20): (graph content, K, label code version).  Landmark
    SAMPLING is itself seeded from the graph content hash
    (:func:`bfs_tpu.serve.labels.sample_landmarks`), so the key needs no
    landmark list — same graph + same K always means the same index."""
    from ..serve.labels import LABELS_VERSION

    return (
        f"labels_k{int(k)}_v{LABELS_VERSION}_s{STORE_VERSION}"
        f"_{graph_content_hash(graph)}"
    )


def load_or_build_labels(graph, k: int, *, cache: LayoutCache | None = None,
                         engine: str = "pull", layout=None,
                         ckpt_dir: str | os.PathLike | None = None):
    """``(LabelIndex, info)`` — the serve label tier's landmark index,
    disk-cached as a sidecar bundle next to the layout bundle (info
    contract: :func:`_load_or_build`).  The K-root sweep itself is
    chunk-checkpointed (:func:`bfs_tpu.serve.labels.build_label_index`),
    so a killed COLD build resumes; a warm hit never recomputes."""
    from ..serve.labels import (
        build_label_index,
        labels_from_arrays,
        labels_to_arrays,
    )

    return _load_or_build(
        graph,
        cache=cache,
        tag=None,
        kind="labels",
        key_fn=lambda: labels_key(graph, k),
        build_fn=lambda: build_label_index(
            graph, k, engine=engine, layout=layout, ckpt_dir=ckpt_dir
        ),
        to_arrays=labels_to_arrays,
        from_arrays=labels_from_arrays,
        build_meta={"engine": engine, "k": int(k)},
    )


def verify_labels_bundle(graph, k: int, *,
                         cache: LayoutCache | None = None) -> dict:
    """Integrity report of the label sidecar bundle WITHOUT building on a
    miss (the cache_warm ``--labels`` check): loads the bundle — every
    array fingerprint-checked by :meth:`LayoutCache.load` — then
    validates the label invariants the oracle leans on: version/shape
    agreement with the graph, landmark ids in range, each landmark at
    distance 0 from itself and its own parent, and the unreachable
    sentinel agreeing between dist and parent.  Returns a JSON-ready
    dict; never raises on a bad bundle."""
    from ..serve.labels import LABEL_INF, LABELS_VERSION, labels_from_arrays

    cache = cache if cache is not None else LayoutCache()
    key = labels_key(graph, k)
    loaded = cache.load(key)
    if loaded is None:
        return {"key": key, "ok": False, "status": "absent"}
    _doc, arrays = loaded
    try:
        idx = labels_from_arrays(arrays)
    except Exception as exc:  # version bump / shape drift
        return {"key": key, "ok": False, "status": f"unreadable: {exc}"}
    problems = []
    dims = np.asarray(arrays["dims"])
    if int(dims[0]) != LABELS_VERSION:
        problems.append(f"labels version {int(dims[0])} != {LABELS_VERSION}")
    if idx.num_vertices != graph.num_vertices:
        problems.append(
            f"num_vertices {idx.num_vertices} != graph "
            f"{graph.num_vertices}"
        )
    if idx.dist.shape != (idx.k, idx.num_vertices):
        problems.append(f"dist shape {idx.dist.shape} != (K, V)")
    if idx.parent.shape != idx.dist.shape:
        problems.append("parent shape differs from dist")
    lm = np.asarray(idx.landmarks)
    if lm.size and (
        int(lm.min()) < 0 or int(lm.max()) >= idx.num_vertices
    ):
        problems.append("landmark id outside the vertex space")
    if not problems and lm.size:
        rows = np.arange(idx.k)
        if np.asarray(idx.dist)[rows, lm].any():
            problems.append("a landmark is not at distance 0 from itself")
        if (np.asarray(idx.parent)[rows, lm] != lm).any():
            problems.append("a landmark is not its own parent")
        sent = np.asarray(idx.dist) == LABEL_INF
        orphan = np.asarray(idx.parent) < 0
        if (sent != orphan).any():
            problems.append(
                "unreachable sentinel disagrees between dist and parent"
            )
    return {
        "key": key,
        "ok": not problems,
        "status": "ok" if not problems else "; ".join(problems),
        "k": int(idx.k),
        "index_bytes": int(idx.nbytes),
        "device_bytes": int(idx.device_bytes),
    }


# ---------------------------------------------------------------------------
# Phase-probe verdict memo (ISSUE 15 satellite): probe_phase_kernels is a
# pure function of (layout shapes, kernel/probe sources, backend, knobs) —
# serve cold-start used to re-pay its K-loops per registered graph even when
# the layout bundle itself warm-hit.  Verdicts are tiny JSON files stored
# content-keyed next to the layout bundles.
# ---------------------------------------------------------------------------

#: Source files whose bytes key the probe verdict: the kernels and the
#: probe itself — an arm implementation change must re-probe.
_PROBE_SOURCES = (
    "ops/relay.py", "ops/relay_pallas.py", "ops/relay_mxu.py",
    "profiling.py",
)

#: Knob env keying the probe verdict — DERIVED from the registry
#: (``affects`` contains ``probe``); KNB002 proves membership against
#: bfs_tpu/knobs.py instead of a hand list.
_PROBE_ENV = knobs.flavor_env("probe")


def probe_verdict_key(eng) -> str:
    """Content key of one engine's probe verdict: layout geometry (the
    probe's operand shapes), expansion-arm geometry when tiles exist,
    kernel sources, jax version + backend + device kind, and the knob
    env."""
    import jax

    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.blake2b(digest_size=16)
    for rel in _PROBE_SOURCES:
        try:
            with open(os.path.join(pkg, rel), "rb") as f:
                h.update(f.read())
        except OSError:
            h.update(b"missing:" + rel.encode())
    rg = eng.relay_graph
    geo = (
        rg.vr, rg.net_size, rg.vperm_size,
        tuple((c.width, c.va, c.vb, c.sa, c.sb, c.vertex_major)
              for c in rg.in_classes),
        bool(eng.packed),
    )
    if eng.adj_tiles is not None:
        geo = geo + (eng.adj_tiles.nt, eng.adj_tiles.vtp, eng.adj_tiles.rtp)
    h.update(repr(geo).encode())
    dev = jax.devices()[0]
    h.update(
        f"{jax.__version__}|{jax.default_backend()}|"
        f"{getattr(dev, 'device_kind', '?')}".encode()
    )
    for knob in _PROBE_ENV:
        h.update(f"{knob}={os.environ.get(knob, '')}".encode())
    return f"probe_{h.hexdigest()}"


def _probe_dir(root: str | None = None) -> str:
    return os.path.join(root or default_root(), "probe")


def load_probe_verdict(key: str, root: str | None = None) -> dict | None:
    path = os.path.join(_probe_dir(root), f"{key}.json")
    try:
        with open(path) as f:
            doc = json.load(f)
        if doc.get("key") != key:
            raise ValueError("probe verdict key mismatch")
        bump_artifact("phase_probe_memo_hits")
        return doc["verdict"]
    except OSError:
        return None
    except Exception as exc:
        logger.warning("dropping corrupt probe verdict %s: %s", key, exc)
        try:
            os.remove(path)
        except OSError:
            pass
        return None


def save_probe_verdict(key: str, verdict: dict,
                       root: str | None = None) -> None:
    d = _probe_dir(root)
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"{key}.json")
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"key": key, "created": time.time(), "verdict": verdict},
                  f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    bump_artifact("phase_probe_memo_writes")


def load_or_build_pull(graph, *, k: int | None = None, row_multiple: int = 64,
                       cache: LayoutCache | None = None,
                       tag: str | None = None):
    """``(PullGraph, info)`` — disk-cached build of the ELL pull layout
    (info contract: :func:`_load_or_build`)."""
    from ..graph.ell import (
        DEFAULT_K,
        build_pull_graph,
        pull_from_arrays,
        pull_to_arrays,
    )

    k = DEFAULT_K if k is None else int(k)
    return _load_or_build(
        graph,
        cache=cache,
        tag=tag,
        kind="pull",
        key_fn=lambda: pull_key(graph, k, row_multiple),
        build_fn=lambda: build_pull_graph(graph, k=k, row_multiple=row_multiple),
        to_arrays=pull_to_arrays,
        from_arrays=pull_from_arrays,
    )
