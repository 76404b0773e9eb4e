#!/usr/bin/env python3
"""Bring-up proof: drive bfs_tpu's main path once on a TPU and verify it.

    python chip_smoke.py                 # one chip: traversal, MXU, multi, serve
    python chip_smoke.py --chips 4       # four chips: the mesh phase only

Everything runs in this one process (a chip belongs to one process), on
one seeded Graph500-parameter R-MAT graph.  Every answer is checked
against the native C++ oracle: distances bit for bit, parent trees with
the algs4 ``check()``.  Every kernel arm is forced (``BFS_TPU_PALLAS=1``,
``BFS_TPU_ROWMIN=pallas``, ``BFS_TPU_STATE_UPDATE=pallas``), so a kernel
that fails raises instead of being deselected.

Each phase prints its wall time, compile time, the bytes on the device
and what it verified.  The last line of standard output is the JSON
object ``{"ok": true, "device": {...}}``; any exception, wrong answer or
fallback exits non-zero without it.  There is no CPU path: without a TPU
the script exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import Counter

#: Kernel arms forced for the whole run: a forced arm that fails raises.
FORCED_ARMS = {
    "BFS_TPU_PALLAS": "1",
    "BFS_TPU_ROWMIN": "pallas",
    "BFS_TPU_STATE_UPDATE": "pallas",
    "BFS_TPU_EXPANSION": "gather",
}


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a persistent-cache hit skips the backend compile),
    and the names of the programs that missed the cache or were not
    written to it, from JAX's compiler log."""

    def __init__(self):
        import logging

        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.missed: list[str] = []
        meter = self

        class MissLog(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
                    meter.missed.append("miss " + msg.split("'")[1])
                elif msg.startswith("Not writing persistent cache entry"):
                    meter.missed.append("not written: " + msg[35:])
                elif record.levelno >= logging.WARNING:
                    print(self.format(record), file=sys.stderr)

        # The compiler logs these at DEBUG: keep them here, off stderr.
        log = logging.getLogger("jax._src.compiler")
        log.addHandler(MissLog(logging.DEBUG))
        log.setLevel(logging.DEBUG)
        log.propagate = False

        def on_duration(event, duration, **_):
            if event.endswith("backend_compile_duration"):
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return (self.seconds, self.compiles, self.cache_hits,
                self.cache_misses, len(self.missed))


class Phase:
    """Times one phase and prints its line: wall and compile seconds,
    device bytes (``memory_stats()``) and what it verified."""

    def __init__(self, name: str, meter: CompileMeter, device):
        self.name, self.meter, self.device = name, meter, device
        self.facts: dict = {}

    def __enter__(self):
        _log(f"phase {self.name}: start")
        self.t0 = time.perf_counter()
        self.c0 = self.meter.snapshot()
        return self

    def __exit__(self, exc_type, exc, tb):
        wall = time.perf_counter() - self.t0
        c1 = self.meter.snapshot()
        stats = self.device.memory_stats() or {}
        line = {
            "phase": self.name,
            "ok": exc_type is None,
            "wall_s": round(wall, 3),
            "compile_s": round(c1[0] - self.c0[0], 3),
            "compiles": c1[1] - self.c0[1],
            "cache_hits": c1[2] - self.c0[2],
            "cache_misses": c1[3] - self.c0[3],
            "missed": dict(Counter(self.meter.missed[self.c0[4]:])),
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            **self.facts,
        }
        _log(json.dumps(line))
        return False


class Oracle:
    """Native C++ oracle distances for one host graph, memoized per root."""

    def __init__(self, graph):
        self.graph = graph
        self._dist: dict[int, object] = {}
        self._canon: dict[int, tuple] = {}

    def dist(self, root: int):
        from bfs_tpu.oracle.native import native_bfs

        if root not in self._dist:
            self._dist[root] = native_bfs(self.graph, root)[0]
        return self._dist[root]

    def canonical(self, root: int):
        """The oracle's min-parent tree, the one every engine builds."""
        from bfs_tpu.oracle.native import native_bfs

        if root not in self._canon:
            self._canon[root] = native_bfs(self.graph, root,
                                           policy="canonical")[:2]
        return self._canon[root]

    def verify_exact(self, root: int, dist, parent, what: str) -> None:
        """Distances and parents bit for bit against the canonical tree."""
        import numpy as np

        for name, got, want in zip(("distances", "parents"), (dist, parent),
                                   self.canonical(root)):
            got = np.asarray(got)
            if got.shape != want.shape or not np.array_equal(got, want):
                raise AssertionError(
                    f"{what}: root {root}: {name} differ from the oracle"
                )

    def verify(self, root: int, dist, parent, what: str) -> None:
        """Distances bit for bit, then the algs4 ``check()`` on the tree."""
        import numpy as np

        from bfs_tpu.oracle.bfs import check

        want = self.dist(root)
        got = np.asarray(dist)
        if got.shape != want.shape or not np.array_equal(got, want):
            bad = int(np.count_nonzero(got != want)) if got.shape == want.shape else -1
            raise AssertionError(
                f"{what}: root {root}: {bad} distances differ from the oracle"
            )
        violations = check(self.graph, got, np.asarray(parent), root)
        if violations:
            raise AssertionError(f"{what}: root {root}: {violations[:3]}")


def make_graph(scale: int, edge_factor: int, seed: int):
    """Graph500-parameter R-MAT from the in-repo native generator."""
    import numpy as np

    from bfs_tpu.graph.csr import Graph
    from bfs_tpu.graph.native_gen import rmat_edges_native

    u, v = rmat_edges_native(scale, edge_factor, seed=seed)
    return Graph(1 << scale, np.concatenate([u, v]), np.concatenate([v, u]))


def giant_roots(graph, oracle: Oracle, count: int, seed: int) -> list[int]:
    """``count`` seeded roots in the component of the max-degree vertex."""
    import numpy as np

    from bfs_tpu.graph.csr import INF_DIST

    hub = int(np.argmax(np.bincount(graph.src, minlength=graph.num_vertices)))
    reached = np.flatnonzero(oracle.dist(hub) != INF_DIST)
    rng = np.random.default_rng(seed)
    return [int(r) for r in rng.choice(reached, size=count, replace=False)]


def build_layout(graph):
    """The default (device) relay layout builder; a fallback to the host
    builder is a failure here."""
    from bfs_tpu.cache.layout import LayoutCache, load_or_build_relay

    rg, info = load_or_build_relay(graph, cache=LayoutCache())
    stages = info.get("build_stages") or {}
    if info.get("builder") != "device" or "fallback" in stages:
        raise AssertionError(f"layout not built by the device builder: {info}")
    return rg, info


def phase_traversal(graph, rg, oracle, roots, ph: Phase) -> None:
    import bfs_tpu
    from bfs_tpu.ops.relay_pallas import pallas_interpret

    if pallas_interpret():
        raise AssertionError("Pallas kernels would run in interpret mode")
    for root in roots:
        res = bfs_tpu.bfs(rg, root, engine="relay")
        oracle.verify(root, res.dist, res.parent, "relay")
    ph.facts["roots_verified"] = len(roots)


def phase_mxu(graph, rg, oracle, roots, args, ph: Phase) -> None:
    """One forced-MXU search; steps the scale down while the tile bundle
    is over budget, and says at which scale it ran."""
    import bfs_tpu

    os.environ["BFS_TPU_EXPANSION"] = "mxu"
    os.environ["BFS_TPU_MXU_KERNEL"] = "pallas"
    try:
        scale, layout = args.scale, rg
        while True:
            try:
                res = bfs_tpu.bfs(layout, roots[0], engine="relay")
                break
            except ValueError as exc:
                if "BFS_TPU_MXU_TILE_GB" not in str(exc) or scale <= 10:
                    raise
                scale -= 1
                _log(f"mxu tiles over budget ({exc}); trying scale {scale}")
                graph = make_graph(scale, args.edge_factor, args.seed)
                oracle = Oracle(graph)
                roots = giant_roots(graph, oracle, 1, args.seed)
                # From the host graph the engine takes the host layout
                # builder, which compiles no programs for the new shapes.
                layout = graph
        oracle.verify(roots[0], res.dist, res.parent, "mxu")
    finally:
        os.environ["BFS_TPU_EXPANSION"] = FORCED_ARMS["BFS_TPU_EXPANSION"]
        os.environ.pop("BFS_TPU_MXU_KERNEL", None)
    ph.facts.update(mxu_scale=scale, roots_verified=1)


def phase_multi(graph, rg, oracle, roots, args, ph: Phase) -> None:
    """``bfs_multi`` from 64 sources; four of the trees checked."""
    import numpy as np

    import bfs_tpu
    from bfs_tpu.graph.csr import INF_DIST

    hub_reach = np.flatnonzero(oracle.dist(roots[0]) != INF_DIST)
    rng = np.random.default_rng(args.seed + 1)
    sources = rng.choice(hub_reach, size=64, replace=False).astype(np.int32)
    res = bfs_tpu.bfs_multi(rg, sources, engine="relay")
    for i in np.linspace(0, 63, 4).astype(int):
        oracle.verify(int(sources[i]), res.dist[i], res.parent[i], "multi")
    ph.facts.update(sources=64, roots_verified=4)


def phase_serve(graph, oracle, roots, args, ph: Phase) -> None:
    """32 seeded dist/path point queries through ``BfsServer`` (default
    ``max_batch``) with the label tier on; the queries the labels leave
    ride one element-major tick of 32 sources.  No breaker trip,
    host-oracle answer or quarantine."""
    import numpy as np

    from bfs_tpu.cache.layout import LayoutCache
    from bfs_tpu.graph.csr import INF_DIST
    from bfs_tpu.serve.registry import GraphRegistry
    from bfs_tpu.serve.server import BfsServer

    os.environ["BFS_TPU_LABELS"] = "16"
    rng = np.random.default_rng(args.seed + 2)
    queries = [
        (roots[i % len(roots)], int(rng.integers(graph.num_vertices)), i % 2)
        for i in range(32)
    ]
    edge_keys = np.sort(
        graph.src.astype(np.int64) * graph.num_vertices + graph.dst
    )
    registry = GraphRegistry(layout_cache=LayoutCache())
    with BfsServer(registry, engine="relay") as srv:
        srv.register("g", graph)
        srv.pause()  # admit every query, then serve them in one tick
        futures = [
            srv.query_path("g", u, v) if want_path else srv.query_dist("g", u, v)
            for u, v, want_path in queries
        ]
        srv.resume()
        replies = [f.result(timeout=1200) for f in futures]
        metrics = srv.metrics
        batch = metrics.report()["batch_size_max"]
        counts = {
            k: metrics.count(k)
            for k in ("oracle_served", "breaker_opened", "device_errors",
                      "label_verify_failures", "label_build_errors",
                      "label_budget_rejects", "label_hits", "label_fallbacks")
        }
    methods: dict[str, int] = {}
    for (u, v, want_path), rep in zip(queries, replies):
        want = int(oracle.dist(u)[v])
        if rep.dist != want:
            raise AssertionError(f"serve dist({u},{v}) = {rep.dist}, oracle {want}")
        if want_path and want != INF_DIST:
            path = np.asarray(rep.path, dtype=np.int64)
            keys = path[:-1] * graph.num_vertices + path[1:]
            pos = np.minimum(np.searchsorted(edge_keys, keys), len(edge_keys) - 1)
            if (path[0] != u or path[-1] != v or len(path) != want + 1
                    or not np.array_equal(edge_keys[pos], keys)):
                raise AssertionError(f"serve path({u},{v}) is not a shortest path")
        methods[rep.method] = methods.get(rep.method, 0) + 1
    for k in ("oracle_served", "breaker_opened", "device_errors",
              "label_verify_failures", "label_build_errors",
              "label_budget_rejects"):
        if counts[k]:
            raise AssertionError(f"serve counted {k}={counts[k]}: {counts}")
    if not counts["label_hits"]:
        raise AssertionError(f"label tier answered nothing: {counts}")
    if batch != 32:
        # The server's default max_batch is 32: the queries the labels do
        # not answer ride one tick padded to 32 sources, which runs the
        # element-major program (serve/executor.py).
        raise AssertionError(f"device tick of {batch} sources, not 32")
    ph.facts.update(queries_verified=len(replies), device_batch=batch,
                    methods=methods, **counts)


def phase_mesh(graph, oracle, roots, args, ph: Phase) -> None:
    """The 1D sharded relay on x4 and the 2x2 grid, against the oracle."""
    from bfs_tpu.graph.relay import build_sharded_relay_graph
    from bfs_tpu.parallel.grid import bfs_grid, make_grid_mesh
    from bfs_tpu.parallel.sharded import bfs_sharded, make_mesh

    t0 = time.perf_counter()
    srg = build_sharded_relay_graph(graph, 4)
    ph.facts["sharded_layout_s"] = round(time.perf_counter() - t0, 3)
    mesh1d = make_mesh(4)
    grid = make_grid_mesh(2, 2)
    for mesh in (mesh1d, grid):
        ids = {d.id for d in mesh.devices.flat}
        if len(ids) != 4:
            raise AssertionError(f"mesh spans {len(ids)} devices, not 4")
    devs = {d.id for m in (mesh1d, grid) for d in m.devices.flat}
    # Both meshes build the canonical min-parent tree: comparing it bit
    # for bit is stronger than check() and a fraction of its host time.
    for root in roots:
        res = bfs_sharded(srg, root, mesh=mesh1d, engine="relay")
        oracle.verify_exact(root, res.dist, res.parent, "sharded x4")
        res = bfs_grid(srg, root, mesh=grid, direction="auto",
                       exchange="auto")
        oracle.verify_exact(root, res.dist, res.parent, "grid 2x2")
    ph.facts.update(roots_verified=len(roots), devices=sorted(devs))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh phase, on four chips")
    # Scale 20: the cold s22 run took 1762 s on the v5e, past the
    # 15-minute limit (CHANGES.md, PR 21).
    ap.add_argument("--scale", type=int, default=20)
    ap.add_argument("--edge-factor", type=int, default=16)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--roots", type=int, default=8)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {platform!r}); "
              "this script runs only on a TPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    os.environ.update(FORCED_ARMS)

    from bfs_tpu.config import compile_cache_dir, enable_compile_cache

    # Every program goes to the persistent cache, so a warm rerun
    # compiles nothing.
    enable_compile_cache(min_compile_seconds=0.0)
    meter = CompileMeter()
    dev = devices[0]
    _log(json.dumps({
        "device_kind": dev.device_kind, "devices": len(devices),
        "chips": args.chips, "scale": args.scale,
        "edge_factor": args.edge_factor, "seed": args.seed,
        "compile_cache": compile_cache_dir(), "jax": jax.__version__,
    }))
    t_start = time.perf_counter()

    with Phase("graph", meter, dev) as ph:
        graph = make_graph(args.scale, args.edge_factor, args.seed)
        oracle = Oracle(graph)
        roots = giant_roots(graph, oracle, args.roots, args.seed)
        ph.facts.update(vertices=graph.num_vertices,
                        directed_edges=int(graph.src.shape[0]), roots=roots)

    if args.chips == 4:
        with Phase("mesh", meter, dev) as ph:
            phase_mesh(graph, oracle, roots, args, ph)
    else:
        with Phase("layout", meter, dev) as ph:
            rg, info = build_layout(graph)
            ph.facts.update(
                builder=info["builder"], cache=info["cache"],
                net_size=int(rg.net_size), vr=int(rg.vr),
                relay_operand_bytes=int(rg.net_masks.nbytes
                                        + rg.vperm_masks.nbytes),
            )
        with Phase("traversal", meter, dev) as ph:
            phase_traversal(graph, rg, oracle, roots, ph)
        with Phase("mxu", meter, dev) as ph:
            phase_mxu(graph, rg, oracle, roots, args, ph)
        with Phase("multi", meter, dev) as ph:
            phase_multi(graph, rg, oracle, roots, args, ph)
        del rg
        with Phase("serve", meter, dev) as ph:
            phase_serve(graph, oracle, roots, args, ph)

    _log(f"all phases verified in {time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
