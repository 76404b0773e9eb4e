"""Structured per-superstep metrics and TEPS accounting.

The reference's observability is a per-iteration elapsed-time log line
(``Elapsed time [i] ==> ...``, BfsSpark.java:112) plus the per-superstep
state files themselves.  Here each superstep records frontier size, newly
settled vertices, and wall time; the run-level summary reports traversed
edges per second (TEPS, Graph500 convention: directed edge count / total BFS
time), the metric named in BASELINE.json.

The serving layer (``bfs_tpu.serve``) adds REQUEST-level metrics on top of
the run-level ones: every admitted query leaves a :class:`QueryRecord`
(queue wait, batch size it rode in, compile/result-cache hits, superstep
count, end-to-end latency) and :class:`ServeMetrics` aggregates them into
the throughput/latency report (p50/p99, queries/sec, cache hit rates).

The ARTIFACT caches (ISSUE 2: layout bundles, serialized executables) get
process-global hit/miss counters here — :func:`bump_artifact` /
:func:`artifact_report` — because their callers span layers (graph build,
engine init, serve registry, bench) that share no metrics object; every
report surface (bench details, serve report, cache_warm) includes them so
a cold-path regression shows up as a miss count, not a silent stall.
"""

from __future__ import annotations

import json
import threading
from dataclasses import dataclass, field, asdict

from ..analysis.runtime import make_lock

_artifact_lock = threading.Lock()
_artifact_counters: dict[str, int] = {}  # guarded-by: _artifact_lock


def bump_artifact(name: str, by: int = 1) -> None:
    """Count one artifact-cache event (e.g. ``layout_cache_hits``).
    Thread-safe, process-global."""
    with _artifact_lock:
        _artifact_counters[name] = _artifact_counters.get(name, 0) + by


def artifact_report() -> dict:
    """Snapshot of the artifact-cache counters plus derived hit rates
    (``None`` when a cache saw no traffic this process)."""
    with _artifact_lock:
        out: dict = dict(_artifact_counters)
    for cache in ("layout_cache",):
        h, m = out.get(f"{cache}_hits", 0), out.get(f"{cache}_misses", 0)
        out[f"{cache}_hit_rate"] = h / (h + m) if h + m else None
    return out


@dataclass
class SuperstepRecord:
    level: int
    frontier_size: int
    seconds: float


@dataclass
class RunMetrics:
    """Accumulated metrics for one BFS run."""

    num_vertices: int = 0
    num_edges: int = 0  # directed
    supersteps: list[SuperstepRecord] = field(default_factory=list)

    def record(self, level: int, frontier_size: int, seconds: float) -> None:
        self.supersteps.append(SuperstepRecord(level, frontier_size, seconds))

    @property
    def total_seconds(self) -> float:
        return sum(r.seconds for r in self.supersteps)

    @property
    def num_levels(self) -> int:
        return len(self.supersteps)

    @property
    def vertices_settled(self) -> int:
        return sum(r.frontier_size for r in self.supersteps)

    def teps(self, *, num_traversals: int = 1) -> float:
        """Traversed edges / second; ``num_traversals`` scales for batched
        multi-source runs (each source traverses the edge set once)."""
        t = self.total_seconds
        return (self.num_edges * num_traversals / t) if t > 0 else float("inf")

    def to_json(self) -> str:
        d = asdict(self)
        d["total_seconds"] = self.total_seconds
        d["teps"] = self.teps()
        return json.dumps(d)

    def log_lines(self):
        """Per-iteration lines in the reference's log style
        (BfsSpark.java:112)."""
        for r in self.supersteps:
            yield (
                f"Elapsed time [{r.level}] ==> {r.seconds * 1e3:.3f} ms "
                f"(frontier {r.frontier_size})"
            )


def percentile(values, q: float) -> float:
    """Linear-interpolation percentile (``q`` in [0, 100]) of a sequence;
    0.0 on an empty input.  Dependency-free so report paths never pull in
    numpy for a handful of scalars."""
    vals = sorted(float(v) for v in values)
    if not vals:
        return 0.0
    if len(vals) == 1:
        return vals[0]
    pos = (len(vals) - 1) * (q / 100.0)
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    frac = pos - lo
    return vals[lo] * (1.0 - frac) + vals[hi] * frac


@dataclass
class QueryRecord:
    """Per-request structured record, attached to every served reply.

    ``status`` is one of ``'ok'`` (device batch), ``'result_cache'`` (LRU
    hit, never queued), ``'oracle'`` (sequential degradation), ``'timeout'``
    or ``'error'``.  ``compile_hit`` is None for paths that never reach the
    executable cache (cache hits, oracle, failures before dispatch)."""

    graph: str = ""
    engine: str = ""
    status: str = "ok"
    epoch: int = 0  # graph epoch the answer was computed against (ISSUE 9)
    num_sources: int = 1
    batch_size: int = 0  # padded device batch the request rode in
    supersteps: int = 0
    queue_wait_s: float = 0.0  # admission -> batch formation
    service_s: float = 0.0  # device (or oracle) execution, batch-shared
    total_s: float = 0.0  # admission -> reply
    compile_hit: bool | None = None
    result_cache_hit: bool = False


class ServeMetrics:
    """Thread-safe aggregator for the serving layer.

    Counters are free-form (``bump('evictions')``) and exact for the
    process lifetime; query records feed the latency/batching statistics
    and are kept in a BOUNDED window (``max_records``, default 100k) so a
    server that "answers searches forever" cannot leak memory through its
    own observability — percentiles are therefore over the most recent
    window, which is what a serving dashboard wants anyway.  ``report()``
    returns a JSON-ready dict — the loadgen and ``run_serve`` print it
    verbatim."""

    def __init__(self, max_records: int = 100_000):
        from collections import deque

        self._lock = make_lock("metrics._lock")
        self.records: deque[QueryRecord] = deque(maxlen=max_records)  # guarded-by: _lock
        self.counters: dict[str, int] = {}  # guarded-by: _lock
        self._first_ts: float | None = None  # guarded-by: _lock
        self._last_ts: float | None = None  # guarded-by: _lock
        # One registry to find them (ISSUE 6): every ServeMetrics is
        # weakly visible in the process-global MetricsRegistry snapshot,
        # so loadgen/chaos/dashboards read ONE surface instead of
        # threading per-server objects around.
        from ..obs.registry import get_registry

        get_registry().register_serve(self)

    def bump(self, name: str, by: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + by

    def count(self, name: str) -> int:
        with self._lock:
            return self.counters.get(name, 0)

    def record_query(self, rec: QueryRecord, *, ts: float | None = None) -> None:
        with self._lock:
            self.records.append(rec)
            if ts is not None:
                if self._first_ts is None:
                    self._first_ts = ts
                self._last_ts = ts

    @staticmethod
    def _rate(counters: dict, hits: str, misses: str) -> float | None:
        h, m = counters.get(hits, 0), counters.get(misses, 0)
        return h / (h + m) if h + m else None

    def report(self) -> dict:
        with self._lock:
            records = list(self.records)
            counters = dict(self.counters)
            span = (
                (self._last_ts - self._first_ts)
                if self._first_ts is not None and self._last_ts is not None
                else 0.0
            )
        ok = [r for r in records if r.status in ("ok", "result_cache", "oracle")]
        lat = [r.total_s for r in ok]
        waits = [r.queue_wait_s for r in records if r.batch_size > 0]
        batches = [r.batch_size for r in records if r.batch_size > 0]
        out = {
            "queries": len(records),
            "served": len(ok),
            "timeouts": sum(r.status == "timeout" for r in records),
            "errors": sum(r.status == "error" for r in records),
            "latency_p50_ms": percentile(lat, 50) * 1e3,
            "latency_p99_ms": percentile(lat, 99) * 1e3,
            "latency_mean_ms": (sum(lat) / len(lat) * 1e3) if lat else 0.0,
            "queue_wait_p99_ms": percentile(waits, 99) * 1e3,
            "batch_size_mean": (sum(batches) / len(batches)) if batches else 0.0,
            "batch_size_max": max(batches, default=0),
            "queries_per_sec": (len(ok) / span) if span > 0 else 0.0,
            "counters": counters,
        }
        # Resilience counters, surfaced explicitly (not just inside the
        # free-form counter dict): a dashboard needs retries-vs-degradations
        # at a glance — rising device_retries with zero device_errors is a
        # flaky-but-recovering transport; rising device_errors means the
        # oracle is quietly serving what the device should.
        out["retries"] = {
            "device_retries": counters.get("device_retries", 0),
            "device_retry_successes": counters.get("device_retry_successes", 0),
            "device_errors": counters.get("device_errors", 0),
        }
        out["compile_hit_rate"] = self._rate(
            counters, "compile_hits", "compile_misses"
        )
        out["result_cache_hit_rate"] = self._rate(
            counters, "result_cache_hits", "result_cache_misses"
        )
        # Process-global artifact caches (layout bundles, executables): a
        # serving dashboard wants cold-path health next to the hot-path
        # latencies — a second process re-registering a graph should show
        # a layout_cache hit here, not a 434 s rebuild.
        out["artifact_caches"] = artifact_report()
        return out

    def to_json(self) -> str:
        return json.dumps(self.report(), indent=2, sort_keys=True)
