"""Micro-batching BFS query server: coalesce, execute once, fan out.

The serving model follows what BLEST-style batched traversal engines and
the repo's own batched multi-source measurements (BENCHMARKS.md config 5)
say about TPU BFS throughput: one batched program over S sources costs
barely more than one source, so the way to serve a stream of independent
queries is to admit them into a bounded queue, coalesce up to ``max_batch``
sources per tick into ONE call of the batched engine, and fan the rows back
out per request.  The whole loop is a single daemon thread; JAX dispatch
stays single-threaded (device work parallelism comes from the batch axis,
not host threads).

Robustness semantics:

  * **backpressure** — a full admission queue raises :class:`AdmissionError`
    at submit time instead of queueing unboundedly;
  * **deadlines** — a request whose deadline expires before its batch is
    formed completes with :class:`QueryTimeout`; an expired-in-flight
    request still gets its (correct) answer, since the batch was already
    paid for — expiry can never yield a wrong answer, only a late or
    missing one;
  * **cancellation** — ``future.cancel()`` before batch formation works;
    cancelled requests are skipped at batch time;
  * **retry** — a TRANSIENT device-path failure (tunnel drop, dispatch
    timeout, UNAVAILABLE window — the classifier lives in
    :mod:`bfs_tpu.resilience.retry`) is retried with capped exponential
    backoff + jitter, bounded by the batch's earliest request deadline,
    before any degradation; a permanent failure (shape error, OOM, plain
    bug) skips the retries entirely;
  * **degradation** — graphs at or under ``oracle_max_vertices`` vertices,
    and any batch whose device path fails permanently (or exhausts its
    retries), are served by the sequential oracle (canonical min-parent,
    bit-exact with the engines) when the host graph is available.

Every reply carries a :class:`~bfs_tpu.utils.metrics.QueryRecord`; the
server-level :class:`~bfs_tpu.utils.metrics.ServeMetrics` aggregates the
latency/batching/cache statistics the loadgen prints.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from dataclasses import dataclass, field

import numpy as np

from .. import knobs
from ..analysis.runtime import make_lock
from ..graph.csr import INF_DIST
from ..models.bfs import check_sources
from ..models.multisource import MultiBfsResult, collapse_multi_source
from ..obs.spans import span as obs_span
from ..resilience.faults import fault_point
from ..resilience.retry import RetryPolicy, retry_call
from ..utils.metrics import QueryRecord, ServeMetrics
from .executor import (
    ExecutableCache,
    bucket_for,
    build_batch_runner,
    run_oracle_batch,
)
from .health import HungCallError, ServeHealth
from .registry import ENGINES, GraphRegistry

logger = logging.getLogger(__name__)

#: Default device-path retry shape: short delays (a serving tick is
#: latency-bound) and few attempts; callers pass ``retry_policy`` for a
#: different shape or ``RetryPolicy(max_attempts=1)`` to disable retries.
DEFAULT_RETRY_POLICY = RetryPolicy(
    max_attempts=3, base_delay_s=0.02, max_delay_s=0.5
)


class ServeError(RuntimeError):
    """Base class for serving-layer failures."""


class AdmissionError(ServeError):
    """The bounded admission queue is full — retry later (backpressure)."""


class QueryTimeout(ServeError):
    """The request's deadline expired before its batch was formed."""


class ServerClosed(ServeError):
    """The server was shut down before the request could be served."""


class CircuitOpenError(ServeError):
    """The executable's circuit is open and no degraded path exists (the
    graph was registered layout-only, so there is no host oracle)."""


@dataclass
class ServeReply:
    """One served query.  ``dist``/``parent`` are int32[V] for single-source
    and collapsed multi-source queries, int32[S, V] for ``mode='tree'``."""

    graph: str
    engine: str
    mode: str
    sources: np.ndarray
    dist: np.ndarray
    parent: np.ndarray
    num_levels: int
    record: QueryRecord


def _parent_chain(parent: np.ndarray, u: int, v: int) -> list | None:
    """Path ``[u, ..., v]`` from a single-source parent tree rooted at
    ``u`` (walks v's parent pointers back to the root)."""
    chain = [int(v)]
    cur = int(v)
    limit = int(parent.shape[-1])
    while cur != u:
        cur = int(parent[cur])
        if cur < 0 or len(chain) > limit:
            return None
        chain.append(cur)
    return chain[::-1]


@dataclass
class DistReply:
    """One point-distance query (``query_dist``).  ``method`` records the
    tier that produced the answer: ``'labels'`` (tight certificate —
    provably exact), ``'exact'`` (traversal fallback), or
    ``'labels_verified'`` (a sampled tight answer that was ALSO checked
    against the traversal before shipping)."""

    graph: str
    u: int
    v: int
    dist: int
    method: str
    landmark: int | None = None
    path: list | None = None


@dataclass
class _Request:
    graph: str
    engine: str
    mode: str  # 'single' | 'tree' | 'collapse'
    sources: np.ndarray
    future: Future
    submitted_at: float
    deadline: float | None
    oracle: bool  # tiny-graph degradation decided at admission
    rec: object = None  # pinned RegisteredGraph snapshot (epoch at admission)
    pinned: bool = False  # pin outstanding; released once via _unpin
    cache_key: tuple | None = None
    record: QueryRecord = field(default_factory=QueryRecord)


# Batch padding lives with the executable cache it keys:
# :func:`bfs_tpu.serve.executor.bucket_for` (the coalescing budget, not the
# bucket function, bounds the input; a single oversized multi-source query
# is allowed through as its own batch).


class BfsServer:
    """In-process BFS query-serving engine over a :class:`GraphRegistry`.

    ``tick_s`` is the coalescing window: after the first request of a tick
    arrives the batcher waits up to ``tick_s`` for more before executing
    (0 = greedy drain of whatever is already queued, the test default).
    """

    def __init__(
        self,
        registry: GraphRegistry | None = None,
        *,
        engine: str = "pull",
        max_batch: int = 32,
        tick_s: float = 0.0,
        queue_depth: int = 256,
        result_cache_size: int = 256,
        exe_cache_size: int = 64,
        oracle_max_vertices: int = 0,
        metrics: ServeMetrics | None = None,
        retry_policy: RetryPolicy | None = None,
        breaker_failures: int = 3,
        breaker_cooldown_s: float = 5.0,
        watchdog_s: float = 60.0,
        watchdog_multiplier: float = 8.0,
        watchdog_min_s: float = 1.0,
        watchdog_compile_floor_s: float = 1200.0,
        verify_sample: int = 0,
    ):
        if engine not in ENGINES:
            raise ValueError(f"unknown engine {engine!r}; use one of {ENGINES}")
        self.metrics = metrics if metrics is not None else ServeMetrics()
        self.registry = (
            registry if registry is not None else GraphRegistry(metrics=self.metrics)
        )
        # Lock-guarded handoff: registry.metrics is shared state and a
        # second server attaching to the same registry raced the bare
        # read-then-write this used to be (found by the LCK pass).
        self.registry.attach_metrics(self.metrics)
        self.default_engine = engine
        self.max_batch = int(max_batch)
        self.tick_s = float(tick_s)
        self.queue_depth = int(queue_depth)
        self.oracle_max_vertices = int(oracle_max_vertices)
        self.retry_policy = (
            retry_policy if retry_policy is not None else DEFAULT_RETRY_POLICY
        )
        self.exe_cache = ExecutableCache(exe_cache_size, metrics=self.metrics)
        # The self-healing authority (ISSUE 9): circuit breaker per
        # compiled executable, hung-call watchdog, sampled on-device
        # integrity checks.  One object so the device path consults one
        # gate; all its state transitions land in self.metrics.
        self._health = ServeHealth(  # immutable after init
            metrics=self.metrics,
            breaker_failures=breaker_failures,
            breaker_cooldown_s=breaker_cooldown_s,
            watchdog_s=watchdog_s,
            watchdog_multiplier=watchdog_multiplier,
            watchdog_min_s=watchdog_min_s,
            compile_floor_s=watchdog_compile_floor_s,
            verify_sample=verify_sample,
        )
        # Epoch-retirement hook: per-epoch breaker cells / latency windows
        # / checkers die with the epoch's device state, so periodic hot
        # swaps never grow health state (or the report payload) unboundedly.
        # A LISTENER, not an attribute overwrite — servers sharing one
        # registry each subscribe their own health; close() detaches.
        self.registry.add_retire_listener(self._health.forget_epoch)
        # Label oracle tier (ISSUE 20): per-(name, epoch) landmark
        # distance-label indexes built at register() time when
        # BFS_TPU_LABELS is on.  The retire listener drops an epoch's
        # index with its device state — an epoch bump can never serve
        # stale labels.
        self._labels: dict[tuple, object] = {}  # guarded-by: _lock
        self._label_tick = 0  # guarded-by: _lock (verify sampling)
        self.registry.add_retire_listener(self._drop_label_epoch)
        # Direction policy resolved ONCE: a malformed BFS_TPU_DIRECTION /
        # alpha / beta knob fails server construction loudly instead of
        # raising inside every tick (which would silently degrade every
        # query to the host oracle).
        from ..models.direction import resolve_direction

        self._direction_key = resolve_direction().key()  # immutable after init
        self._lock = make_lock("server._lock")
        self._cond = threading.Condition(self._lock)  # holding _cond == holding _lock
        self._result_cache: OrderedDict[tuple, tuple] = OrderedDict()  # guarded-by: _lock
        self._result_cache_size = int(result_cache_size)  # immutable after init
        self._pending: deque[_Request] = deque()  # guarded-by: _lock
        self._paused = False  # guarded-by: _lock
        self._closed = False  # guarded-by: _lock
        self._thread = threading.Thread(
            target=self._serve_loop, name="bfs-serve", daemon=True
        )
        self._thread.start()

    # ----------------------------------------------------------- lifecycle --
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def close(self) -> None:
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout=30)
        with self._cond:
            drained = list(self._pending)
            self._pending.clear()
        for req in drained:
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(ServerClosed("server closed"))
            self._unpin(req)
        # Detach the health hook: a shared registry outlives this server
        # and must not call into its dead ServeHealth.
        self.registry.remove_retire_listener(self._health.forget_epoch)
        self.registry.remove_retire_listener(self._drop_label_epoch)
        with self._lock:
            self._labels.clear()

    def pause(self) -> None:
        """Hold batch formation (admission continues) — lets tests and
        maintenance windows stage a known set of requests per tick."""
        with self._cond:
            self._paused = True

    def resume(self) -> None:
        with self._cond:
            self._paused = False
            self._cond.notify_all()

    # ----------------------------------------------------------- admission --
    def register(self, name: str, graph, **kw):
        """Register — or HOT-SWAP — a graph.  Re-registering an existing
        name creates a new epoch (see :meth:`GraphRegistry.register`):
        queries admitted after this call see the new graph, in-flight
        queries finish on the snapshot they were admitted under, and the
        old epoch's device operands are released when its last in-flight
        reference drops.  Executable and result caches need no purge —
        their keys carry the epoch, so old entries can never serve the
        new graph and age out of their LRUs naturally.

        With ``BFS_TPU_LABELS=<K>`` and a host graph, registration also
        builds (or warm-loads from the layout store's sidecar) the
        landmark distance-label index for the NEW epoch — the hot-swap
        contract extends to the label tier: point queries admitted after
        this call answer from the new index, the old one dies with its
        epoch."""
        rec = self.registry.register(name, graph, **kw)
        self._maybe_build_labels(rec)
        return rec

    def unregister(self, name: str) -> None:
        """Drop a graph AND every cache derived from it.  Use this (not
        ``registry.unregister``) on a server: the compiled executables and
        result LRU entries are keyed by graph name, and a later
        re-registration under the same name must never serve answers — or
        run programs — computed against the old graph."""
        self.registry.unregister(name)
        self.exe_cache.drop_graph(name)
        with self._lock:
            for key in [k for k in self._result_cache if k[0] == name]:
                del self._result_cache[key]
            for key in [k for k in self._labels if k[0] == name]:
                del self._labels[key]

    def query(self, graph: str, source: int, **kw) -> Future:
        """Single-source shortest-path query; reply rows are 1-D."""
        return self.submit(graph, [int(source)], mode="single", **kw)

    def query_multi(
        self, graph: str, sources, *, collapse: bool = True, **kw
    ) -> Future:
        """Multi-source query: ``collapse=True`` serves the oracle's
        multi-source semantics (``dist[v] = min_s dist_s[v]``), else
        independent per-source trees (``mode='tree'``)."""
        return self.submit(
            graph, sources, mode="collapse" if collapse else "tree", **kw
        )

    # ------------------------------------------------------- label tier --
    def _drop_label_epoch(self, name: str, epoch: int) -> None:
        # Retire listener: fires under the registry lock — touch only our
        # own state, never call back into the registry.
        with self._lock:
            self._labels.pop((name, epoch), None)

    def _label_oracle(self, name: str, epoch: int):
        with self._lock:
            return self._labels.get((name, epoch))

    def _maybe_build_labels(self, rec) -> None:
        """Build/load the label index for a freshly registered epoch.
        Label availability is best-effort: a build failure or a budget
        reject logs, bumps a counter, and the server keeps serving
        exact-only — the tier may only ever ADD speed."""
        k = knobs.get("BFS_TPU_LABELS")
        if not k:
            return
        if rec.graph is None:
            self.metrics.bump("label_build_skipped")
            return
        from .labels import LabelBudgetError, build_label_oracle

        try:
            # The sweep runs on the server's engine and its registered
            # layout: a second layout build per graph would double the
            # register cost (a host relay build at scale is minutes).
            engine = self.default_engine
            oracle, info = build_label_oracle(
                rec.graph, k, cache=self.registry.layout_cache,
                engine=engine, layout=self.registry.layout(rec.name, engine),
            )
        except LabelBudgetError as exc:
            logger.warning("label index over budget: %s", exc)
            self.metrics.bump("label_budget_rejects")
            return
        except Exception:
            logger.warning(
                "label index build failed; serving exact-only",
                exc_info=True,
            )
            self.metrics.bump("label_build_errors")
            return
        with self._lock:
            self._labels[(rec.name, rec.epoch)] = oracle
        self.metrics.bump("label_builds")
        self.metrics.bump(
            "label_build_cache_hits" if info.get("cache") == "hit"
            else "label_build_cache_misses"
        )

    def query_dist(self, graph: str, u: int, v: int, *,
                   want_path: bool = False, **kw) -> Future:
        """Point query ``dist(u, v)`` — the label oracle tier.

        Tight label answers (provably exact via the triangle-inequality
        certificate) resolve IMMEDIATELY from the device-resident index —
        no traversal, no batch queue, same fast-path shape as a result
        cache hit.  Non-tight pairs, and graphs registered without labels
        (``BFS_TPU_LABELS=off``), chain onto the exact traversal path
        (:meth:`query` from ``u``, every robustness property included).
        Every ``BFS_TPU_LABELS_VERIFY``-th tight answer is ALSO re-derived
        through the exact path and cross-checked before shipping; a
        mismatch quarantines the index (label_verify_failures) and the
        exact answer ships instead — sampled verification, like every
        other serve reply.  Returns a Future resolving to
        :class:`DistReply`; ``want_path`` additionally reconstructs a
        shortest path (label tier: through the certifying landmark;
        fallback: from the traversal's parent tree)."""
        u, v = int(u), int(v)
        rec = self.registry.get(graph)
        check_sources(rec.num_vertices, np.asarray([u, v], dtype=np.int32))
        oracle = self._label_oracle(graph, rec.epoch)
        if oracle is not None:
            d, tight, best_k = oracle.dist_one(u, v)
            if tight:
                self.metrics.bump("label_hits")
                path = oracle.path(u, v) if want_path else None
                verify_every = knobs.get("BFS_TPU_LABELS_VERIFY")
                if verify_every > 0:
                    with self._lock:
                        self._label_tick += 1
                        sample = self._label_tick % verify_every == 0
                    if sample:
                        return self._verify_label_answer(
                            graph, rec.epoch, u, v, d, best_k, path, **kw
                        )
                fut: Future = Future()
                fut.set_result(DistReply(
                    graph, u, v, d, "labels",
                    landmark=int(oracle.index.landmarks[best_k]),
                    path=path,
                ))
                return fut
            self.metrics.bump("label_fallbacks")
        else:
            self.metrics.bump("label_misses")
        return self._exact_dist(graph, u, v, want_path, **kw)

    def query_path(self, graph: str, u: int, v: int, **kw) -> Future:
        """Shortest-path point query; sugar for ``query_dist(...,
        want_path=True)`` — exact path through the certifying landmark
        when the label bound is tight, traversal parent-chain otherwise."""
        return self.query_dist(graph, u, v, want_path=True, **kw)

    def _exact_dist(self, graph: str, u: int, v: int, want_path: bool,
                    **kw) -> Future:
        """Chain a point query onto the exact traversal path."""
        outer: Future = Future()
        inner = self.submit(graph, [u], mode="single", **kw)

        def _done(f: Future):
            try:
                reply = f.result()
            except BaseException as exc:
                outer.set_exception(exc)
                return
            try:
                d = int(reply.dist[v])
                path = (
                    _parent_chain(reply.parent, u, v)
                    if want_path and d < INF_DIST else None
                )
                outer.set_result(DistReply(
                    graph, u, v, d, "exact", path=path
                ))
            except BaseException as exc:  # defensive: never hang the future
                outer.set_exception(exc)

        inner.add_done_callback(_done)
        return outer

    def _verify_label_answer(self, graph: str, epoch: int, u: int, v: int,
                             label_d: int, best_k: int, path,
                             **kw) -> Future:
        """Sampled cross-check: re-derive the answer through the exact
        path and compare before shipping.  A mismatch drops the epoch's
        index (it can never be trusted again) and ships the EXACT answer."""
        outer: Future = Future()
        inner = self._exact_dist(graph, u, v, False, **kw)

        def _done(f: Future):
            try:
                exact = f.result()
            except BaseException as exc:
                outer.set_exception(exc)
                return
            if exact.dist != label_d:
                self.metrics.bump("label_verify_failures")
                logger.error(
                    "label answer mismatch on %s: dist(%d,%d) labels=%d "
                    "exact=%d — quarantining the label index",
                    graph, u, v, label_d, exact.dist,
                )
                self._drop_label_epoch(graph, epoch)
                outer.set_result(exact)
                return
            self.metrics.bump("label_verifies")
            outer.set_result(DistReply(
                graph, u, v, label_d, "labels_verified", path=path
            ))

        inner.add_done_callback(_done)
        return outer

    def submit(
        self,
        graph: str,
        sources,
        *,
        mode: str = "single",
        engine: str | None = None,
        timeout_s: float | None = None,
    ) -> Future:
        """Admit one query; returns a :class:`concurrent.futures.Future`
        resolving to a :class:`ServeReply` (or raising
        :class:`QueryTimeout` / :class:`ServerClosed`).

        Raises :class:`AdmissionError` immediately when the bounded queue
        is full, and ``ValueError``/``KeyError`` for malformed requests —
        admission errors are the caller's, never the batcher's."""
        if mode not in ("single", "tree", "collapse"):
            raise ValueError(f"unknown mode {mode!r}")
        engine = engine or self.default_engine
        if engine not in ENGINES:
            raise ValueError(f"unknown engine {engine!r}; use one of {ENGINES}")
        # Pin the CURRENT epoch at admission: this is the snapshot the
        # caller observed, and the pin is what keeps it alive (layouts +
        # device operands) through a hot swap until the reply lands.
        rec = self.registry.pin(graph)
        req: _Request | None = None
        try:
            sources = np.atleast_1d(np.asarray(sources, dtype=np.int32))
            if sources.ndim != 1:
                raise ValueError("sources must be a scalar or 1-D sequence")
            if mode == "single" and sources.shape[0] != 1:
                raise ValueError("mode='single' takes exactly one source")
            check_sources(rec.num_vertices, sources)
            now = time.monotonic()
            future: Future = Future()
            oracle = (
                rec.graph is not None
                and rec.num_vertices <= self.oracle_max_vertices
            )
            req = _Request(
                graph=graph,
                engine=engine,
                mode=mode,
                sources=sources,
                future=future,
                submitted_at=now,
                deadline=(now + float(timeout_s)) if timeout_s is not None else None,
                oracle=oracle,
                rec=rec,
                pinned=True,
            )
            req.cache_key = (
                graph, rec.epoch, engine, mode, tuple(sources.tolist())
            )
            cached = self._result_cache_get(req.cache_key)
            if cached is not None:
                dist, parent, num_levels = cached
                self.metrics.bump("result_cache_hits")
                rec_q = QueryRecord(
                    graph=graph,
                    engine=engine,
                    status="result_cache",
                    epoch=rec.epoch,
                    num_sources=int(sources.shape[0]),
                    result_cache_hit=True,
                )
                self.metrics.record_query(rec_q, ts=time.monotonic())
                future.set_result(
                    ServeReply(graph, engine, mode, sources, dist, parent,
                               num_levels, rec_q)
                )
                self._unpin(req)
                return future
            self.metrics.bump("result_cache_misses")
            with self._cond:
                if self._closed:
                    raise ServerClosed("server is closed")
                if len(self._pending) >= self.queue_depth:
                    self.metrics.bump("rejected")
                    raise AdmissionError(
                        f"admission queue full ({self.queue_depth} pending)"
                    )
                self._pending.append(req)
                self._cond.notify_all()
        except BaseException:
            # Rejected/invalid requests never reached the queue: balance
            # the admission pin before the error propagates.
            if req is not None:
                self._unpin(req)
            else:
                self.registry.unpin(rec)
            raise
        return future

    def _unpin(self, req: _Request) -> None:
        """Release a request's epoch pin exactly once (every completion
        path — reply, timeout, cancel, close, batch failure — funnels
        through here; idempotent so overlapping paths are safe)."""
        if req.pinned:
            req.pinned = False
            self.registry.unpin(req.rec)

    # --------------------------------------------------------- result cache --
    def _result_cache_get(self, key):
        with self._lock:
            hit = self._result_cache.get(key)
            if hit is not None:
                self._result_cache.move_to_end(key)
            return hit

    def _result_cache_put(self, key, value) -> None:
        if self._result_cache_size <= 0 or key is None:
            return
        with self._lock:
            self._result_cache[key] = value
            self._result_cache.move_to_end(key)
            while len(self._result_cache) > self._result_cache_size:
                self._result_cache.popitem(last=False)

    # ------------------------------------------------------------- batching --
    def _serve_loop(self) -> None:
        while True:
            with self._cond:
                while not self._closed and (self._paused or not self._pending):
                    self._cond.wait(timeout=0.1)
                if self._closed:
                    return
                first = self._pending.popleft()
            if self.tick_s > 0:
                # Coalescing window: give concurrent submitters a tick to
                # land in the same batch before the shapes are fixed.
                time.sleep(self.tick_s)
            batch = [first]
            budget = self.max_batch - first.sources.shape[0]
            with self._cond:
                keep: deque[_Request] = deque()
                while self._pending:
                    req = self._pending.popleft()
                    compatible = (
                        req.rec is first.rec  # same graph AND same epoch:
                        # a batch never mixes snapshots across a hot swap
                        and req.engine == first.engine
                        and req.oracle == first.oracle
                        and req.sources.shape[0] <= budget
                    )
                    if compatible:
                        batch.append(req)
                        budget -= req.sources.shape[0]
                    else:
                        keep.append(req)
                self._pending.extendleft(reversed(keep))
            try:
                # One span per executed tick batch: with the eviction
                # markers and the metrics snapshot this is the serve
                # loop's complete Perfetto story (coalesce -> execute ->
                # fan out); empty ticks never reach here, so the buffer
                # only grows with real work.
                with obs_span(
                    "serve.batch",
                    graph=batch[0].graph,
                    engine=batch[0].engine,
                    requests=len(batch),
                ):
                    self._execute_batch(batch)
            except Exception as exc:  # defensive: the loop must survive
                for req in batch:
                    if not req.future.done():
                        req.future.set_exception(exc)
            finally:
                # Every request that entered a tick releases its epoch pin
                # here, whatever path it took (reply, timeout, cancel,
                # batch failure) — _unpin is idempotent, and this is the
                # hook that lets a swapped-out epoch free its HBM.
                for req in batch:
                    self._unpin(req)

    def _execute_batch(self, batch: list[_Request]) -> None:
        formed_at = time.monotonic()
        live: list[_Request] = []
        for req in batch:
            if not req.future.set_running_or_notify_cancel():
                self.metrics.bump("cancelled")
                continue
            if req.deadline is not None and formed_at > req.deadline:
                self._finish_timeout(req, formed_at)
                continue
            live.append(req)
        if not live:
            return
        first = live[0]
        all_sources = np.concatenate([r.sources for r in live])
        padded = bucket_for(all_sources.shape[0])
        # The batch executes against the epoch its requests were ADMITTED
        # under (every req in a batch shares one pinned rec — the coalescer
        # requires it): a hot swap between admission and execution must not
        # change the answer.
        rec = first.rec
        # One circuit per compiled executable; the exe key adds the
        # direction policy because that is a compile-time input, not a
        # health property.
        circuit_key = (first.graph, rec.epoch, first.engine, padded)
        exe_key = (
            first.graph, rec.epoch, first.engine, padded,
            self._direction_key,
        )
        compile_hit: bool | None = None
        status = "ok"
        device_attempted = False
        t0 = time.monotonic()

        def _oracle_tick():
            # The sequential fallback, shared by every degraded path.
            # Padding exists only for compiled-shape stability; the
            # sequential path runs the real sources, nothing more.
            self.metrics.bump("oracle_served")
            return run_oracle_batch(rec.graph, all_sources), "oracle", \
                all_sources.shape[0]

        try:
            if first.oracle:
                result, status, padded = _oracle_tick()
            elif not self._health.allow(circuit_key):
                # Circuit open: this executable failed permanently
                # ``breaker_failures`` ticks in a row (or was quarantined
                # by a failed integrity verdict).  Short-circuit straight
                # to the degraded path — no retry loop, no watchdog wait —
                # until the cooldown admits a canary.
                self.metrics.bump("breaker_short_circuits")
                if rec.graph is None:
                    raise CircuitOpenError(
                        f"circuit open for {circuit_key} and graph "
                        f"{first.graph!r} was registered layout-only — no "
                        "host oracle to degrade to"
                    )
                result, status, padded = _oracle_tick()
            else:
                sources_padded = np.concatenate(
                    [all_sources,
                     np.full(padded - all_sources.shape[0], all_sources[0],
                             dtype=np.int32)]
                )
                deadlines = [r.deadline for r in live if r.deadline is not None]

                def _device_tick():
                    def _guarded():
                        nonlocal compile_hit
                        # The direction policy (resolved ONCE at server
                        # init — a malformed knob fails construction,
                        # never a tick) is part of the executable key
                        # (ISSUE 7): today the relay batch runner reads
                        # the same env at build, so the key keeps a
                        # stale-program reuse impossible when the knob
                        # changes across server restarts; when the batch
                        # programs grow in-program switching the key is
                        # already right.  Auto-switching itself is an
                        # IN-program lax.cond — steady-state ticks never
                        # retrace however often the schedule flips
                        # direction.
                        runner, compile_hit = self.exe_cache.get(
                            exe_key,
                            lambda: build_batch_runner(
                                self.registry, first.graph, first.engine,
                                padded, epoch=rec.epoch,
                            ),
                        )
                        # ``raise:serve.batch`` = a classified-permanent
                        # device fault; ``delay:serve.batch:N`` = a wedged
                        # XLA call the watchdog must catch.
                        fault_point("serve.batch")
                        return runner(sources_padded)

                    # The watchdog deadline is p99-informed per circuit
                    # key and tightened by the batch's earliest request
                    # deadline — a wedged call times out (HungCallError,
                    # permanent) instead of freezing the serve thread.
                    # The BUILD runs inside the guarded call too: a wedged
                    # compile must degrade the tick, not freeze the loop —
                    # a cold tick's budget is floored at compile_floor_s
                    # so an honest minutes-long compile never trips it.
                    return self._health.run_guarded(
                        circuit_key, _guarded, deadlines,
                        describe=f"device batch ({first.graph}/{first.engine})",
                        cold=exe_key not in self.exe_cache,
                    )

                retried = {"n": 0}

                def _on_retry(attempt, exc, delay):
                    retried["n"] += 1
                    self.metrics.bump("device_retries")

                # Transient failures (tunnel drop, UNAVAILABLE window) get
                # a bounded backoff retry BEFORE any oracle degradation —
                # previously one flake degraded the whole tick.  Bounded by
                # the batch's earliest deadline: a tick with 50 ms left
                # must not sleep 500 ms to find out.
                device_attempted = True
                # ISSUE 14 hung-call resume: a watchdog timeout abandons
                # only the attempt THREAD; when the runner is a
                # checkpointing SegmentedBatchRunner its completed
                # segments survive as in-process epochs, so another
                # attempt RESUMES mid-traversal instead of recomputing
                # from the roots.  Re-attempt only while progress
                # actually advances (a wedge at the same superstep twice
                # means the device path is dead — degrade) and the batch
                # deadline has not passed.
                resume_progress = None
                while True:
                    try:
                        result = retry_call(
                            _device_tick,
                            policy=self.retry_policy,
                            deadline_s=(
                                min(deadlines) - time.monotonic()
                                if deadlines else None
                            ),
                            on_retry=_on_retry,
                            describe=(
                                f"device batch ({first.graph}/"
                                f"{first.engine})"
                            ),
                        )
                        break
                    except HungCallError:
                        runner0 = self.exe_cache.peek(exe_key)
                        prog_fn = getattr(runner0, "ckpt_progress", None)
                        progress = prog_fn() if callable(prog_fn) else None
                        past_deadline = bool(deadlines) and (
                            time.monotonic() >= min(deadlines)
                        )
                        if (
                            progress is None
                            or progress == resume_progress
                            or past_deadline
                        ):
                            raise
                        resume_progress = progress
                        self.metrics.bump("ckpt_hung_resumes")
                if retried["n"]:
                    self.metrics.bump("device_retry_successes")
                self._health.record_success(circuit_key)
                # Sampled production integrity check: every Kth executed
                # device tick re-verifies one answered root on device
                # (~28-byte verdict pull).  A failed verdict is proof the
                # executable is wrong — quarantine it (force-open the
                # circuit AND drop the cached runner so the half-open
                # canary rebuilds rather than re-probes the same artifact)
                # and re-run this batch on the fallback path.
                verdict = self._health.maybe_verify(rec, result, all_sources)
                if verdict is not None:
                    # maybe_verify only samples when rec.graph is present,
                    # so the oracle re-run below always has a host graph.
                    self._health.quarantine(
                        circuit_key, f"integrity verdict {verdict}"
                    )
                    self.exe_cache.drop_key(exe_key)
                    # A proven-wrong executable may already have fed the
                    # result LRU on unsampled ticks (verify_sample > 1):
                    # purge this graph epoch's cached answers too, or the
                    # quarantine serves known-bad results as cache hits.
                    with self._lock:
                        for k in [
                            k for k in self._result_cache
                            if k[0] == first.graph and k[1] == rec.epoch
                        ]:
                            del self._result_cache[k]
                    result, status, padded = _oracle_tick()
                    compile_hit = None
        except Exception as exc:
            if device_attempted:
                # Permanent failure or exhausted transient retries: one
                # more consecutive strike against this executable (after
                # ``breaker_failures`` of them the circuit opens and later
                # ticks skip straight to the degraded path).
                self._health.record_failure(circuit_key, repr(exc))
            if rec.graph is None:
                raise
            # Device path failed permanently (OOM, lowering, a real bug) or
            # exhausted its transient retries: degrade to the sequential
            # oracle EXACTLY ONCE rather than failing the whole tick.
            self.metrics.bump("device_errors")
            result, status, padded = _oracle_tick()
            compile_hit = None
        service_s = time.monotonic() - t0
        self.metrics.bump("batches")

        row = 0
        for req in live:
            s = req.sources.shape[0]
            rows = slice(row, row + s)
            row += s
            sub = MultiBfsResult(
                sources=req.sources,
                dist=result.dist[rows],
                parent=result.parent[rows],
                num_levels=result.num_levels,
            )
            if req.mode == "collapse":
                dist, parent = collapse_multi_source(sub)
            elif req.mode == "single":
                dist, parent = sub.dist[0], sub.parent[0]
            else:
                dist, parent = sub.dist, sub.parent
            done = time.monotonic()
            req.record = QueryRecord(
                graph=req.graph,
                engine=req.engine,
                status=status,
                epoch=rec.epoch,
                num_sources=s,
                batch_size=padded,
                supersteps=result.num_levels,
                queue_wait_s=formed_at - req.submitted_at,
                service_s=service_s,
                total_s=done - req.submitted_at,
                compile_hit=compile_hit,
            )
            reply = ServeReply(
                req.graph, req.engine, req.mode, req.sources,
                dist, parent, result.num_levels, req.record,
            )
            self._result_cache_put(req.cache_key, (dist, parent, result.num_levels))
            self.metrics.record_query(req.record, ts=done)
            req.future.set_result(reply)

    def _finish_timeout(self, req: _Request, now: float) -> None:
        req.record = QueryRecord(
            graph=req.graph,
            engine=req.engine,
            status="timeout",
            num_sources=int(req.sources.shape[0]),
            queue_wait_s=now - req.submitted_at,
            total_s=now - req.submitted_at,
        )
        self.metrics.bump("timeouts")
        self.metrics.record_query(req.record, ts=now)
        req.future.set_exception(
            QueryTimeout(
                f"deadline expired after {req.record.total_s * 1e3:.1f} ms "
                "in queue"
            )
        )

    # -------------------------------------------------------------- reports --
    def report(self) -> dict:
        out = self.metrics.report()
        epochs = {}
        for n in self.registry.names():
            # names() and epoch() are two lock acquisitions: a concurrent
            # unregister between them must shrink the snapshot, not crash
            # the monitoring caller.
            try:
                epochs[n] = self.registry.epoch(n)
            except KeyError:
                continue
        out["registry"] = {
            "graphs": list(epochs),
            "epochs": epochs,
            "resident_bytes": self.registry.resident_bytes(),
            "resident": [list(k) for k in self.registry.resident_keys()],
            "evictions": self.registry.evictions,
            "evictions_deferred": self.registry.evictions_deferred,
            "budget_bytes": self.registry.device_budget_bytes,
        }
        out["executables_cached"] = len(self.exe_cache)
        with self._lock:
            out["labels"] = {
                f"{name}@{epoch}": oracle.report()
                for (name, epoch), oracle in self._labels.items()
            }
        # Breaker snapshot (per-circuit state/failures/open-for) + watchdog
        # budgets + integrity sampling state — the self-healing view the
        # chaos driver asserts its transitions against.
        out["health"] = self._health.report()
        return out
