"""Graph registry: epoch-versioned graphs, budgeted device residency.

The cold-path tax the serving layer exists to amortize is two-fold
(VERDICT round 5: 434 s layout build + ~830 s compile before the first
timed repeat): the HOST layout (ELL packing / dst-sorted edge arrays) and
the DEVICE operand upload.  The registry owns both:

  * host layouts are built once per ``(graph epoch, engine)`` and memoized
    for the epoch's lifetime — they are cheap host RAM; with a
    ``layout_cache`` the build also goes through the persistent on-disk
    bundle store (:mod:`bfs_tpu.cache.layout`), so a SECOND process
    registering the same graph loads the finished layout in seconds
    instead of rebuilding it (ISSUE 2: the 434 s cold relay build);
  * device operands (the multi-GB HBM residents at bench scale) are
    tracked in an LRU keyed ``(name, epoch, engine)`` against an explicit
    byte budget.  Evicting a pull entry calls
    :func:`bfs_tpu.graph.ell.drop_device_operands` — the release hook that
    was dead code until this subsystem — AND drops the registry's own
    reference to the returned ``(ell0, folds)`` tuple, which is what
    actually lets the runtime free the HBM.  The next
    :meth:`GraphRegistry.acquire` re-uploads.

**Epochs (ISSUE 9).**  ``register(name, graph)`` on an existing name no
longer raises — it creates a NEW EPOCH: the current-epoch pointer swaps
atomically, every later admission sees the new snapshot, and the old
epoch's layouts/operands stay alive exactly as long as in-flight work
holds a pin on them.  The contract:

  * :meth:`pin` returns the current :class:`RegisteredGraph` with its
    ref-count bumped; :meth:`unpin` drops it.  The serving layer pins at
    admission and unpins when the reply (or timeout/cancel) lands, so a
    query admitted before a swap is answered against the snapshot it was
    admitted under — hot graph swap without wrong or torn answers.
  * A replaced epoch with pins retires LAZILY: the moment its last pin
    drops, its device operands are evicted and its layouts forgotten
    (``epochs_retired``).  With no pins it retires at swap time.
  * The HBM-budget evictor (:meth:`_make_room`) SKIPS entries whose epoch
    is pinned and counts ``eviction_deferred`` — a graph serving an
    in-flight batch is never evicted mid-tick, so the relay engine (whose
    eviction path drops the whole engine object) cannot be yanked out
    from under a running superstep loop.  The budget may transiently
    overshoot; the next unpinned acquire settles it.

The registry is synchronous and lock-guarded; the serving loop is its only
hot caller, but registration can happen from any thread.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from dataclasses import dataclass, field

import numpy as np

from .. import knobs
from ..analysis.runtime import make_lock
from ..graph.csr import DeviceGraph, Graph, build_device_graph
from ..graph.ell import PullGraph, build_pull_graph, device_ell, drop_device_operands

ENGINES = ("pull", "push", "relay")

#: Knob env keying resident engine operands — DERIVED from the registry
#: (``affects`` contains ``serve``); KNB002 proves membership against
#: bfs_tpu/knobs.py.  A knob flip between acquires (tests flipping
#: BFS_TPU_PACKED, an operator retuning direction thresholds) must never
#: reuse operands resolved under the old flavor — the same stale-key
#: contract the lint caches and the bench journal enforce.
ENGINE_FLAVOR_ENV = knobs.flavor_env("serve")


def _engine_env_fingerprint() -> str:
    """blake2b-6 over the raw serve-affecting knob values — the fourth
    element of the resident-operand LRU key."""
    parts = ";".join(
        f"{n}={knobs.raw(n) or ''}" for n in ENGINE_FLAVOR_ENV
    )
    return hashlib.blake2b(parts.encode(), digest_size=6).hexdigest()


@dataclass
class RegisteredGraph:
    """One registered graph EPOCH: the host graph plus lazily built
    layouts.  ``pins``/``retired`` are guarded by the owning registry's
    lock (this object carries no lock of its own)."""

    name: str
    graph: Graph | None  # host graph; None when registered from a layout
    num_vertices: int = 0
    num_edges: int = 0
    layouts: dict = field(default_factory=dict)  # engine -> layout object
    epoch: int = 0
    pins: int = 0  # in-flight references (registry-lock guarded)
    retired: bool = False  # replaced by a newer epoch (registry-lock guarded)
    #: Resources fully released — ``_retire`` ran, or ``unregister``
    #: force-dropped the record.  Makes release idempotent: a late unpin
    #: after unregister must not re-run ``_retire`` (which would
    #: double-count ``epochs_retired`` and re-fire retire listeners).
    released: bool = False  # registry-lock guarded


def _pull_device_bytes(pg: PullGraph) -> int:
    """HBM bytes :func:`device_ell` will pin for this layout (int32)."""
    return 4 * pg.padded_slots


def _push_device_bytes(dg: DeviceGraph) -> int:
    return 4 * (int(np.asarray(dg.src).size) + int(np.asarray(dg.dst).size))


def _upload(engine: str, layout):
    """An engine's device operands from its host layout."""
    import jax.numpy as jnp

    if engine == "pull":
        return device_ell(layout)
    if engine == "push":
        return (jnp.asarray(layout.src), jnp.asarray(layout.dst))
    return layout  # relay: tensors uploaded at engine init


class GraphRegistry:
    """Named graph epochs + memoized layouts + budgeted device residency.

    ``device_budget_bytes`` caps the summed size of resident device
    operands across all graphs/engines; ``None`` means unlimited (single
    graph, the common case).  The budget never blocks the entry being
    acquired — a single layout larger than the budget is allowed in alone,
    everything else (unpinned) is evicted around it.
    """

    def __init__(
        self,
        *,
        device_budget_bytes: int | None = None,
        metrics=None,
        layout_cache=None,
    ):
        self._lock = make_lock("registry._lock", "rlock")
        self._graphs: dict[str, RegisteredGraph] = {}  # guarded-by: _lock
        # Replaced epochs still pinned by in-flight work, keyed
        # (name, epoch); entries leave when their last pin drops.
        self._retired: dict[tuple[str, int], RegisteredGraph] = {}  # guarded-by: _lock
        # (name, epoch, engine, env fingerprint) -> (bytes,
        # operands-ref); order = LRU.
        self._resident: OrderedDict[
            tuple[str, int, str, str], tuple[int, object]
        ] = OrderedDict()  # guarded-by: _lock
        self.device_budget_bytes = device_budget_bytes  # immutable after init
        self.metrics = metrics  # guarded-by: _lock
        self.evictions = 0  # guarded-by: _lock
        self.evictions_deferred = 0  # guarded-by: _lock
        # Info dict of the most recent relay layout load-or-build (builder
        # flavor, build/load seconds, per-stage timings) for register-time
        # reporting; {} until the first relay layout is built.
        self.last_layout_info: dict = {}  # guarded-by: _lock
        # Persistent layout bundles: a LayoutCache, a directory path, or
        # None (in-process memoization only — the default, so tests and
        # embedders opt in to disk writes explicitly).
        if isinstance(layout_cache, str):
            from ..cache.layout import LayoutCache

            layout_cache = LayoutCache(layout_cache)
        self.layout_cache = layout_cache
        # Retire listeners: each ``fn(name, epoch)`` fires (under the
        # registry lock) once per epoch whose device state is released —
        # at swap time, on the last unpin of a replaced epoch, and for
        # every epoch dropped by :meth:`unregister`.  A LIST, not a slot:
        # multiple servers legitimately share one registry (the same
        # reason ``attach_metrics`` is a guarded handoff), and each points
        # a listener at its own ``ServeHealth.forget_epoch``.  Listeners
        # must never call back into the registry.
        self._retire_listeners: list = []  # guarded-by: _lock
        # Per-name epoch counters that SURVIVE unregister: an in-flight
        # query pinned to the old incarnation's epoch N must never resolve
        # against a re-registered graph that reused N.
        self._next_epoch: dict[str, int] = {}  # guarded-by: _lock

    def add_retire_listener(self, fn) -> None:
        """Subscribe ``fn(name, epoch)`` to epoch retirements (idempotent
        per callable; see the constructor comment for firing semantics)."""
        with self._lock:
            if fn not in self._retire_listeners:
                self._retire_listeners.append(fn)

    def remove_retire_listener(self, fn) -> None:
        """Unsubscribe — a closing server detaches its health hook so a
        shared registry never calls into a dead server."""
        with self._lock:
            if fn in self._retire_listeners:
                self._retire_listeners.remove(fn)

    # ------------------------------------------------------------- graphs --
    def register(
        self,
        name: str,
        graph: Graph | DeviceGraph | PullGraph,
        *,
        engines: tuple[str, ...] = (),
    ) -> RegisteredGraph:
        """Register ``graph`` under ``name``; optionally pre-build layouts.

        Accepts a host :class:`Graph` (all engines available), or a prebuilt
        :class:`PullGraph` / single-shard :class:`DeviceGraph` (that engine
        only; no oracle fallback without the host graph).

        Re-registering an existing name is a HOT SWAP: the new graph
        becomes the next epoch, later admissions see it immediately, and
        in-flight work pinned to the old epoch finishes against the old
        snapshot (whose resources are released when its last pin drops)."""
        if isinstance(graph, PullGraph):
            make = lambda e: RegisteredGraph(  # noqa: E731
                name, None, graph.num_vertices, graph.num_edges,
                {"pull": graph}, epoch=e,
            )
        elif isinstance(graph, DeviceGraph):
            if graph.num_shards != 1:
                raise ValueError("serve registry takes single-shard graphs")
            make = lambda e: RegisteredGraph(  # noqa: E731
                name, None, graph.num_vertices, graph.num_edges,
                {"push": graph}, epoch=e,
            )
        elif isinstance(graph, Graph):
            make = lambda e: RegisteredGraph(  # noqa: E731
                name, graph, graph.num_vertices, graph.num_edges, epoch=e,
            )
        else:
            raise TypeError(f"cannot register {type(graph).__name__}")
        with self._lock:
            old = self._graphs.get(name)
            # Epochs are monotonic per NAME — drawn from a counter that
            # survives unregister, never old.epoch + 1: if numbering
            # restarted at 0 after an unregister/re-register cycle, an
            # in-flight query pinned to the old incarnation's epoch N
            # would silently resolve to the new graph's epoch N and be
            # answered against the wrong snapshot.
            e = self._next_epoch.get(name, 0)
            self._next_epoch[name] = e + 1
            rec = make(e)
            self._graphs[name] = rec
            if old is not None:
                old.retired = True
                if old.pins <= 0:
                    self._retire(old)
                else:
                    self._retired[(name, old.epoch)] = old
                self._bump("epochs_swapped")
                from ..obs import instant

                instant(
                    "registry.swap", graph=name, epoch=rec.epoch,
                    old_epoch=old.epoch, old_pins=old.pins,
                )
        for engine in engines:
            self._layout_for(rec, engine)
        return rec

    def get(self, name: str) -> RegisteredGraph:
        """The CURRENT epoch for ``name``."""
        with self._lock:
            try:
                return self._graphs[name]
            except KeyError:
                raise KeyError(f"graph {name!r} is not registered") from None

    def pin(self, name: str) -> RegisteredGraph:
        """Atomically fetch the current epoch and bump its ref-count.
        The caller MUST balance with :meth:`unpin` (the serving layer pins
        at admission, unpins when the reply lands) — the pin is what keeps
        a swapped-out epoch's snapshot alive for in-flight work."""
        with self._lock:
            rec = self.get(name)
            rec.pins += 1
            return rec

    def unpin(self, rec: RegisteredGraph) -> None:
        """Drop one pin; a retired epoch whose last pin drops releases its
        device operands and layouts here."""
        with self._lock:
            rec.pins -= 1
            if rec.retired and rec.pins <= 0:
                self._retire(rec)

    def get_epoch(self, name: str, epoch: int) -> RegisteredGraph:
        """A SPECIFIC epoch — current or still-pinned retired.  KeyError
        once the epoch is gone (retired with no pins, or unregistered)."""
        with self._lock:
            rec = self._rec_for(name, epoch)
            if rec is None:
                raise KeyError(
                    f"graph {name!r} epoch {epoch} is gone (retired or "
                    "unregistered with no pins outstanding)"
                )
            return rec

    def names(self) -> list[str]:
        with self._lock:
            return list(self._graphs)

    def epoch(self, name: str) -> int:
        """Current epoch number for ``name`` (0 = never swapped)."""
        return self.get(name).epoch

    def unregister(self, name: str) -> None:
        """Drop a graph entirely — every epoch: evict device operands,
        forget layouts.  This is the FORCED path (pins do not defer it;
        in-flight queries on an unregistered graph may fail, which is the
        operator's stated intent — use ``register`` for a safe swap).

        On a :class:`~bfs_tpu.serve.BfsServer`, call ``server.unregister``
        instead — the server also holds compiled executables and result-LRU
        entries keyed by this name that must be invalidated with it."""
        with self._lock:
            for key in [k for k in self._resident if k[0] == name]:
                self._evict(key)
            dropped = []
            rec = self._graphs.pop(name, None)
            if rec is not None:
                dropped.append(rec)
            for k in [k for k in self._retired if k[0] == name]:
                dropped.append(self._retired.pop(k))
            for r in dropped:
                # Mark fully released so a still-in-flight pin's eventual
                # unpin is a no-op — without this, unpin would run _retire
                # a second time (double epochs_retired, double listener
                # fire, and a sweep that could evict a re-registered
                # incarnation's live residents).
                r.retired = True
                r.released = True
                r.layouts.clear()
                for fn in list(self._retire_listeners):
                    fn(name, r.epoch)

    # bfs_tpu: holds _lock
    def _rec_for(self, name: str, epoch: int) -> RegisteredGraph | None:
        rec = self._graphs.get(name)
        if rec is not None and rec.epoch == epoch:
            return rec
        return self._retired.get((name, epoch))

    # bfs_tpu: holds _lock
    def _retire(self, rec: RegisteredGraph) -> None:
        """Release a replaced epoch: evict its resident operands, forget
        its layouts.  Called at swap time (no pins) or from the last
        :meth:`unpin`; idempotent via ``rec.released`` (an unpin landing
        after :meth:`unregister` already dropped the record must not
        release it twice)."""
        if rec.released:
            return
        rec.released = True
        for key in [
            k
            for k in self._resident
            if k[0] == rec.name and k[1] == rec.epoch
        ]:
            self._evict(key, rec)
        self._retired.pop((rec.name, rec.epoch), None)
        rec.layouts.clear()
        self._bump("epochs_retired")
        for fn in list(self._retire_listeners):
            fn(rec.name, rec.epoch)

    # bfs_tpu: holds _lock
    def _bump(self, counter: str, by: int = 1) -> None:
        if self.metrics is not None:
            self.metrics.bump(counter, by)
        from ..obs import get_registry

        get_registry().counter(counter, by)

    # ------------------------------------------------------------ layouts --
    def layout(self, name: str, engine: str):
        """The memoized host layout for the CURRENT epoch of ``name``:
        :class:`PullGraph`, dst-sorted :class:`DeviceGraph`, or a
        :class:`~bfs_tpu.models.bfs.RelayEngine`."""
        return self._layout_for(self.get(name), engine)

    def _layout_for(self, rec: RegisteredGraph, engine: str):
        if engine not in ENGINES:
            raise ValueError(f"unknown engine {engine!r}; use one of {ENGINES}")
        with self._lock:
            layout = rec.layouts.get(engine)
        if layout is not None:
            return layout
        if rec.graph is None:
            raise ValueError(
                f"graph {rec.name!r} was registered as a prebuilt "
                f"{list(rec.layouts)[0]!r} layout; engine {engine!r} needs "
                "the host Graph"
            )
        if engine == "pull":
            layout = self._build_pull(rec.graph)
        elif engine == "push":
            layout = build_device_graph(rec.graph)
        else:  # relay: the engine object IS the layout (it owns its tensors)
            from ..models.bfs import RelayEngine

            layout = RelayEngine(self._build_relay_layout(rec.graph))
        with self._lock:
            # Lost-race double build is possible without holding the lock
            # through the (expensive) build; keep the first one stored.
            layout = rec.layouts.setdefault(engine, layout)
        return layout

    def layout_info(self) -> dict:
        """Snapshot of the most recent relay layout build/load info
        (builder flavor, seconds, per-stage timings); {} before any."""
        with self._lock:
            return dict(self.last_layout_info)

    def attach_metrics(self, metrics) -> None:
        """Adopt a metrics sink unless one is already attached.  The
        lock-guarded form of the ``if registry.metrics is None:
        registry.metrics = ...`` handoff servers used to do bare — two
        servers attaching to one shared registry raced it (LCK001)."""
        with self._lock:
            if self.metrics is None:
                self.metrics = metrics

    def _note_disk(self, info: dict) -> None:
        with self._lock:  # metrics ref is shared; snapshot it under the lock
            metrics = self.metrics
        if metrics is not None and info.get("cache") == "hit":
            metrics.bump("layout_disk_hits")
        elif metrics is not None and info.get("cache") == "miss":
            metrics.bump("layout_disk_misses")

    def _build_pull(self, graph: Graph) -> PullGraph:
        if self.layout_cache is None:
            return build_pull_graph(graph)
        from ..cache.layout import load_or_build_pull

        pg, info = load_or_build_pull(graph, cache=self.layout_cache)
        self._note_disk(info)
        return pg

    def _build_relay_layout(self, graph: Graph):
        """The RelayEngine constructor arg: the disk-cached RelayGraph when
        a layout cache is configured, else the host graph (the engine
        builds the layout itself).  The build info (builder flavor,
        build/load seconds, per-stage timings) is kept in
        ``last_layout_info`` so register-time surfaces (`bfs-tpu-serve`,
        the load generator) can print what graph registration cost."""
        if self.layout_cache is None:
            return graph
        from ..cache.layout import load_or_build_relay

        rg, info = load_or_build_relay(graph, cache=self.layout_cache)
        self._note_disk(info)
        with self._lock:
            self.last_layout_info = dict(info)
        return rg

    # ---------------------------------------------------------- residency --
    def acquire(self, name: str, engine: str):
        """Device operands for the CURRENT epoch of ``(graph, engine)``."""
        return self.acquire_for(self.get(name), engine)

    def acquire_epoch(self, name: str, epoch: int, engine: str):
        """Device operands for a SPECIFIC epoch — the form batch runners
        bound to a pinned snapshot use, so a tick formed before a swap
        executes against its admission-time graph."""
        return self.acquire_for(self.get_epoch(name, epoch), engine)

    def acquire_for(self, rec: RegisteredGraph, engine: str):
        """Device operands for one epoch, uploading within budget.

        Returns the operand handle the executor passes to the compiled
        program: ``(ell0, folds)`` for pull, ``(src, dst)`` device arrays
        for push, the :class:`RelayEngine` itself for relay.  Marks the
        entry most-recently-used and evicts LRU entries (via
        :func:`drop_device_operands` for pull) until the budget holds —
        skipping entries whose epoch is pinned by in-flight work."""
        layout = self._layout_for(rec, engine)
        if engine == "pull":
            nbytes = _pull_device_bytes(layout)
        elif engine == "push":
            nbytes = _push_device_bytes(layout)
        else:
            rg = layout.relay_graph
            nbytes = int(rg.vperm_masks.nbytes + rg.net_masks.nbytes)
        return self._acquire(rec, engine, nbytes,
                             lambda: _upload(engine, layout))

    def acquire_sssp(self, rec: RegisteredGraph, *, max_weight: int,
                     push: bool):
        """SSSP's per-graph device operands for one epoch
        (:func:`bfs_tpu.algo.sssp.graph_operands`: the endpoint hash
        under ``max_weight``, ``dst_ends``, and the out-row view where
        ``push``), built from the resident push operands and held and
        budgeted like them, as their own entry ``sssp/w<max_weight>``
        (``/push`` with the view)."""
        from ..algo.sssp import graph_operands, graph_operands_nbytes

        layout = self._layout_for(rec, "push")
        v = int(layout.num_vertices)
        nbytes = graph_operands_nbytes(v, int(np.asarray(layout.src).size),
                                       push=push)

        def upload():
            src, dst = self.acquire_for(rec, "push")
            return graph_operands(src, dst, v, max_weight=max_weight,
                                  push=push)

        engine = f"sssp/w{int(max_weight)}" + ("/push" if push else "")
        return self._acquire(rec, engine, nbytes, upload)

    def _acquire(self, rec: RegisteredGraph, engine: str, nbytes: int,
                 upload):
        """The residency of :meth:`acquire_for`: the entry ``(rec,
        engine)`` if resident, else ``upload()`` of ``nbytes`` within
        budget."""
        key = (rec.name, rec.epoch, engine, _engine_env_fingerprint())
        with self._lock:
            if key in self._resident:
                self._resident.move_to_end(key)
                # A residency hit still settles any deferred-eviction
                # overshoot: _make_room with 0 incoming evicts unpinned
                # LRU entries until the budget holds again.
                self._make_room(0, keep=key)
                return self._resident[key][1]
            # Make room BEFORE the out-of-lock upload: evicting victims
            # only after the new operands are resident would peak HBM at
            # budget + incoming — the overshoot the budget exists to
            # prevent.  A concurrent acquire racing this window can still
            # transiently overshoot; the hit-path settle reclaims it.
            self._make_room(nbytes, keep=key)
        # The H2D upload runs OUTSIDE the lock: the serve watchdog abandons
        # a wedged device call wherever it stands, and an abandoned worker
        # that died holding this lock would freeze every pin/report/
        # register on every graph — the exact whole-server wedge the
        # watchdog exists to prevent.  A concurrent duplicate upload is
        # harmless (keep-first below; device_ell memoizes on the layout).
        operands = upload()
        with self._lock:
            if key in self._resident:  # lost an upload race: keep first
                self._resident.move_to_end(key)
                return self._resident[key][1]
            if rec.released:
                # The epoch was released while we uploaded outside the
                # lock (a watchdog-abandoned tick's last unpin ran
                # _retire, or an unregister force-dropped the record):
                # its resident keys are already evicted and the release
                # will never run again — caching now would leak the dead
                # snapshot's device arrays for the registry's lifetime.
                # Hand the operands to this (only) caller without
                # inserting.
                return operands
            # Room was made before the upload; re-running _make_room here
            # would double-count a deferral for this one acquire.
            self._resident[key] = (nbytes, operands)
            return operands

    # bfs_tpu: holds _lock
    def _pinned(self, key: tuple[str, int, str, str]) -> bool:
        rec = self._rec_for(key[0], key[1])
        return rec is not None and rec.pins > 0

    # bfs_tpu: holds _lock
    def _make_room(self, incoming: int, *, keep) -> None:
        if self.device_budget_bytes is None:
            return
        while (
            self._resident
            and self.resident_bytes() + incoming > self.device_budget_bytes
        ):
            victim = next(
                (
                    k
                    for k in self._resident
                    if k != keep and not self._pinned(k)
                ),
                None,
            )
            if victim is None:
                if not any(k != keep for k in self._resident):
                    # ``keep`` alone exceeds the budget: that is the
                    # documented single-oversized-layout allowance, not a
                    # deferral — counting it would bump eviction_deferred
                    # on EVERY tick of a supported steady state.
                    return
                # Every other entry is serving an in-flight batch: a
                # mid-tick eviction would yank the relay engine (or churn
                # pull/push re-uploads) out from under running work.
                # Defer — transient budget overshoot, settled by the next
                # unpinned acquire — and make the deferral visible.  Only
                # an actual upload (incoming > 0) counts: the hit-path
                # settle probes with 0 on every tick, and counting those
                # would tick the event counter (and flood the trace with
                # markers) at tick rate for as long as the pins persist.
                if incoming > 0:
                    self.evictions_deferred += 1
                    self._bump("eviction_deferred")
                    from ..obs import instant

                    instant(
                        "registry.evict_deferred",
                        graph=keep[0], engine=keep[2], bytes=incoming,
                    )
                return
            self._evict(victim)

    # bfs_tpu: holds _lock
    def _evict(self, key: tuple[str, int, str, str], rec=None) -> None:
        name, epoch, engine = key[0], key[1], key[2]
        nbytes = self._resident[key][0]
        self._resident.pop(key)  # drops OUR reference to the operands
        # ``rec`` comes from _retire's swap-time path: an unpinned old
        # epoch is already out of _graphs (the new rec replaced it) and
        # never entered _retired, so _rec_for can't see it — without the
        # explicit rec the release hooks below silently skip and an
        # externally-held layout keeps its device memo alive.
        if rec is None:
            rec = self._rec_for(name, epoch)
        layout = rec.layouts.get(engine) if rec else None
        if layout is None:
            pass
        elif engine == "pull":
            drop_device_operands(layout)
        elif engine == "relay":
            # The engine object pins its mask tensors and compiled
            # executables; rebuilding from the host graph is the release
            # path (the RelayGraph host layout would be the thing to keep,
            # but the engine memoizes it internally — drop the whole
            # object and rebuild on next acquire).
            rec.layouts.pop(engine, None)
        # push, sssp/*: the device arrays lived only in the resident entry.
        self.evictions += 1
        if self.metrics is not None:
            self.metrics.bump("evictions")
        # HBM-budget thrash was invisible (ISSUE 6 satellite): every
        # eviction now lands a trace marker AND a registry counter, so a
        # serve process churning its device residency shows up in both the
        # Perfetto timeline and the metrics snapshot, not just as slow
        # re-uploads.
        from ..obs import get_registry, instant

        instant("registry.evict", graph=name, engine=engine, bytes=nbytes)
        get_registry().counter("graph_evictions")
        get_registry().counter("graph_evicted_bytes", nbytes)

    def release(self, name: str, engine: str | None = None) -> None:
        """Explicitly evict one graph's device operands across all epochs
        (all engines when ``engine`` is None).  Host layouts stay
        memoized.  Explicit = forced: pins do not defer this path."""
        with self._lock:
            for key in [
                k
                for k in self._resident
                if k[0] == name and (engine is None or k[2] == engine)
            ]:
                self._evict(key)

    def resident_bytes(self) -> int:
        with self._lock:  # RLock: also safe from _make_room's hot path
            return sum(b for b, _ in self._resident.values())

    def resident_keys(self) -> list[tuple[str, int, str]]:
        """Resident operand identities as (name, epoch, engine), in LRU
        order.  The internal map key additionally carries the engine-env
        fingerprint (:func:`_engine_env_fingerprint`) so a knob-flavor
        change can never reuse a stale engine — but that is a cache-
        correctness detail, not part of the observable identity (the
        same triple may appear once per resident env flavor)."""
        with self._lock:
            return [(k[0], k[1], k[2]) for k in self._resident]
