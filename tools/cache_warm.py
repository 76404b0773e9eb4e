"""Pre-build the bench matrix's persistent artifacts so a driver run is
warm from its first second (ISSUE 2 satellite).

For every scale in the matrix (default: BENCH_SCALE) this
builds-or-loads, in order:

  1. the device-ready R-MAT graph npz (bench.load_or_build);
  2. the relay layout bundle (content-addressed, memmap-loadable —
     bfs_tpu/cache/layout.py) and, with --pull, the ELL pull bundle;
  3. with --compile (TPU backends only), the fused single-source relay
     program, populating JAX's persistent compile cache.

Each step prints its warm/cold status and timing; the final line is the
artifact-cache counter report.  Run it once per (machine, cache dir) —
CI/driver runs then start with every cold cost already paid:

    python tools/cache_warm.py --scales 24,22,20 --compile
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _compare_builders(dg, scale: int, reps: int) -> None:
    """Time ``reps`` interleaved UNCACHED builds per flavor and print the
    medians — the ISSUE 10 build-seconds evidence table
    (BENCHMARKS.md 'Layout build: device vs host')."""
    import statistics

    from bfs_tpu.graph.relay import build_relay_graph
    from bfs_tpu.graph.relay_device import build_relay_graph_device

    build_relay_graph(dg)  # warm both paths once (numpy/native/jit caches)
    stages: dict = {}
    build_relay_graph_device(dg, stage_times=stages)
    host_s, dev_s, deltas = [], [], []
    for i in range(reps):
        # Alternate which flavor builds first: the SECOND build of a pair
        # measures ~2-3 ms slower at toy scale (allocator/cache pollution
        # from its predecessor), so a fixed order would bias the
        # comparison by more than the effect being measured.
        order = ("host", "device") if i % 2 == 0 else ("device", "host")
        pair = {}
        for flavor in order:
            t0 = time.perf_counter()
            if flavor == "host":
                build_relay_graph(dg)
            else:
                build_relay_graph_device(dg)
            pair[flavor] = time.perf_counter() - t0
        host_s.append(pair["host"])
        dev_s.append(pair["device"])
        deltas.append(pair["host"] - pair["device"])
    print(
        json.dumps({
            "scale": scale,
            "reps": reps,
            "host_build_s": {
                "median": statistics.median(host_s), "min": min(host_s),
            },
            "device_build_s": {
                "median": statistics.median(dev_s), "min": min(dev_s),
            },
            "paired_delta_s_median": statistics.median(deltas),
            "device_wins": sum(1 for d in deltas if d > 0),
            "device_stage_seconds": {
                k: round(v, 5) if isinstance(v, float) else v
                for k, v in stages.items()
            },
        }),
        flush=True,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--scales",
        default=None,
        help="comma-separated R-MAT scales (default: BENCH_SCALE)",
    )
    ap.add_argument("--edge-factor", type=int,
                    default=int(os.environ.get("BENCH_EDGE_FACTOR", "6")))
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--block", type=int, default=8 * 1024)
    ap.add_argument("--pull", action="store_true",
                    help="also warm the ELL pull-layout bundles")
    ap.add_argument("--tiles", action="store_true",
                    help="also prebuild + verify the adj-tiles sidecar "
                    "bundle per scale (the streamed arm's host-store "
                    "feed, ISSUE 18): builds through the on-disk layout "
                    "cache, re-loads it fingerprint-checked, and prints "
                    "superblock counts + host-store bytes")
    ap.add_argument("--labels", action="store_true",
                    help="also prebuild + verify the landmark distance-"
                    "label sidecar per scale (the serve label tier's "
                    "index, ISSUE 20): builds through the on-disk layout "
                    "cache, re-loads it fingerprint-checked, and prints "
                    "K, index bytes, and build seconds")
    ap.add_argument("--landmarks", type=int, metavar="K", default=0,
                    help="landmark count for --labels (default: the "
                    "BFS_TPU_LABELS knob, or 32 when that is off)")
    ap.add_argument("--compile", action="store_true",
                    help="also AOT-compile the fused relay program per "
                    "scale (TPU backends; populates the compile cache)")
    ap.add_argument("--builder", choices=("auto", "device", "host"),
                    default="auto",
                    help="relay layout builder flavor for cold builds "
                    "(default: BFS_TPU_LAYOUT_BUILD, i.e. device)")
    ap.add_argument("--compare", type=int, metavar="N", default=0,
                    help="instead of warming, time N interleaved UNCACHED "
                    "builds per flavor per scale and print a "
                    "device-vs-host build-seconds table")
    args = ap.parse_args(argv)

    from bfs_tpu.config import enable_compile_cache

    print(f"caches: {json.dumps(enable_compile_cache())}", flush=True)

    if args.scales:
        scales = [int(s) for s in args.scales.split(",") if s.strip()]
    else:
        scales = [int(os.environ.get("BENCH_SCALE", "24"))]
    scales = sorted(set(scales), reverse=True)

    import jax

    from bfs_tpu.bench import (
        _generator_backend,
        load_or_build,
        load_or_build_pull,
        load_or_build_relay,
    )

    backend = _generator_backend()
    if args.builder != "auto":
        os.environ["BFS_TPU_LAYOUT_BUILD"] = args.builder
    for scale in scales:
        key = (
            f"{backend}_s{scale}_ef{args.edge_factor}_seed{args.seed}"
            f"_block{args.block}"
        )
        t0 = time.perf_counter()
        dg, source = load_or_build(
            scale, args.edge_factor, args.seed, args.block, backend
        )
        print(
            f"s{scale}: graph ready in {time.perf_counter() - t0:.1f}s "
            f"(V={dg.num_vertices} E={dg.num_edges})",
            flush=True,
        )
        if args.compare:
            _compare_builders(dg, scale, args.compare)
            continue
        t0 = time.perf_counter()
        rg, build_seconds = load_or_build_relay(dg, key)
        from bfs_tpu.bench import _LAST_RELAY_INFO

        print(
            f"s{scale}: relay layout ready in {time.perf_counter() - t0:.1f}s "
            f"(cold build was {build_seconds:.1f}s, "
            f"builder={_LAST_RELAY_INFO.get('builder', 'host')})",
            flush=True,
        )
        if args.pull:
            t0 = time.perf_counter()
            load_or_build_pull(dg, key)
            print(
                f"s{scale}: pull layout ready in "
                f"{time.perf_counter() - t0:.1f}s",
                flush=True,
            )
        if args.tiles:
            from bfs_tpu.cache.layout import (
                LayoutCache,
                load_or_build_tiles,
                verify_tiles_bundle,
            )
            from bfs_tpu.stream import HostTileStore

            tile_cache = LayoutCache()
            t0 = time.perf_counter()
            at, tinfo = load_or_build_tiles(rg, cache=tile_cache)
            verdict = verify_tiles_bundle(rg, cache=tile_cache)
            store_report = HostTileStore(at).report()
            print(
                f"s{scale}: adj-tiles sidecar ready in "
                f"{time.perf_counter() - t0:.1f}s "
                f"(cache={tinfo.get('cache')}, "
                f"verify={'ok' if verdict['ok'] else verdict['status']})",
                flush=True,
            )
            print(
                json.dumps({
                    "scale": scale,
                    "tiles_key": verdict["key"],
                    "verify_ok": verdict["ok"],
                    **store_report,
                }),
                flush=True,
            )
            if not verdict["ok"]:
                return 1
        if args.labels:
            from bfs_tpu import knobs
            from bfs_tpu.cache.layout import (
                LayoutCache,
                load_or_build_labels,
                verify_labels_bundle,
            )

            k = args.landmarks or knobs.get("BFS_TPU_LABELS") or 32
            label_cache = LayoutCache()
            t0 = time.perf_counter()
            idx, linfo = load_or_build_labels(dg, k, cache=label_cache)
            lverdict = verify_labels_bundle(dg, k, cache=label_cache)
            print(
                f"s{scale}: label sidecar ready in "
                f"{time.perf_counter() - t0:.1f}s "
                f"(K={idx.k}, index={idx.device_bytes >> 20} MB on device, "
                f"cold build was {linfo.get('build_seconds', -1.0):.1f}s, "
                f"cache={linfo.get('cache')}, "
                f"verify={'ok' if lverdict['ok'] else lverdict['status']})",
                flush=True,
            )
            print(
                json.dumps({
                    "scale": scale,
                    "labels_key": lverdict["key"],
                    "verify_ok": lverdict["ok"],
                    "k": idx.k,
                    "index_bytes": idx.nbytes,
                    "device_bytes": idx.device_bytes,
                    "build_seconds": linfo.get("build_seconds", -1.0),
                }),
                flush=True,
            )
            if not lverdict["ok"]:
                return 1
        if args.compile:
            if jax.default_backend() != "tpu":
                print(
                    f"s{scale}: --compile skipped (backend is "
                    f"{jax.default_backend()}, the Pallas programs are TPU-only)",
                    flush=True,
                )
            else:
                from bfs_tpu.models.bfs import RelayEngine

                t0 = time.perf_counter()
                eng = RelayEngine(rg, sparse_hybrid=False)
                _ = int(eng.run_many_device([source])[-1].level)
                print(
                    f"s{scale}: fused program compiled + warm in "
                    f"{time.perf_counter() - t0:.1f}s "
                    f"(applier={eng.applier})",
                    flush=True,
                )

    from bfs_tpu.utils.metrics import artifact_report

    print(json.dumps({"artifact_caches": artifact_report()}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
