"""What every driver of ``benchmark/drivers/`` shares: the run's inputs,
the record of what it measured, and the small tools of a measured loop.

A driver is ``benchmark/drivers/<driver>.py``, named by a traffic mix's
``driver`` key.  Its ``drive(ctx, **swap)`` builds the system under test
through the program's public entry points, warms every shape its traffic
uses, measures for ``ctx.seconds``, then (with the window closed and the
memory peak read) frees the program's state and checks the answers
against ``reference.py``.  It returns a :class:`Run`, from which the
metric readers of ``benchmark/metrics/`` take their numbers.  Its
``control()`` returns the ``swap`` that puts the control of the check in
the program's place (``control.py``).  It may define ``tiny(params) ->
params``, the cut of its traffic mix that the CPU tests run.

A closed loop of searches, whatever kernel it drives, shares its window
and its edge count: the driver writes its engine, an ``ask(sources) ->
(dist[B, n], parent[B, n])`` into it, its check and its control, and
hands ``ask`` to :func:`closed_window`; :func:`traversed_edges` then
gives the Graph500 edge count of the searches the window completed, the
one that ``teps`` reads.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable

import numpy as np

from benchmark import harness
from benchmark.trace import traced_window


@dataclass
class Context:
    """One run's inputs, as ``run.py`` hands them to a driver."""

    config: dict
    #: the traffic mix's parameters
    params: dict
    seed: int
    seconds: float
    #: where the window's profiler trace goes, or None for an untraced run
    trace_dir: str | None
    meter: object
    t_start: float
    devices: list
    #: the configuration's graph maker: ``edges(graph) -> (n, u, v)``
    make_edges: Callable


@dataclass
class Run:
    """What one run measured; the metric readers' only input."""

    #: the driver that made it
    kind: str
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    #: closed loops: searches (trees) and requests completed in the window
    searches: int = 0
    requests: int = 0
    traversed_edges: int = 0
    layout: dict = field(default_factory=dict)
    peaks: dict = field(default_factory=dict)
    trace: object = None
    checks: dict = field(default_factory=dict)
    #: the result's ``device``, read before the reference runs
    device: dict = field(default_factory=dict)
    facts: dict = field(default_factory=dict)


def annotate(name: str):
    """A host span in the profiler's trace."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Reservoir:
    """A uniform sample of ``size`` items of a stream of unknown length
    (Vitter's algorithm R), drawn from the run's seed."""

    def __init__(self, size: int, gen: np.random.Generator):
        self.size, self.gen = int(size), gen
        self.items: list = []
        self.seen = 0

    def wants(self) -> int | None:
        """The slot the next item goes to, or None to skip it."""
        j = self.seen
        self.seen += 1
        if j < self.size:
            self.items.append(None)
            return j
        r = int(self.gen.integers(0, j + 1))
        return r if r < self.size else None


def closed_window(ctx: Context, run: Run, requests: np.ndarray,
                  ask: Callable, sample: Reservoir) -> np.ndarray:
    """The measured window of a closed loop of searches: one caller sends
    ``requests[i % R]`` (int32[R, B] keys) through ``ask`` as soon as the
    last request has returned, until ``ctx.seconds`` have passed, and
    offers each tree to ``sample`` as a ``(root, dist, parent)`` copy made
    inside the window.  Set-up ends where the window starts:
    ``run.setup_s`` and the set-up's compiles are taken here.  Fills the
    window's seconds, requests, searches and attempts, notes the compiles
    inside the window, and reads the device's memory peak before the
    driver frees its state.  Returns how often each request was sent."""
    before = ctx.meter.snapshot()
    run.facts["setup_compile"] = before
    run.setup_s = time.perf_counter() - ctx.t_start
    sent = np.zeros(len(requests), np.int64)
    i = 0
    t0 = time.perf_counter()
    with traced_window(ctx.trace_dir):
        while True:
            sources = requests[i % len(requests)]
            with annotate("bench.request"):
                dist, parent = ask(sources)
            for t, root in enumerate(sources.tolist()):
                slot = sample.wants()
                if slot is not None:
                    sample.items[slot] = (root, dist[t].copy(), parent[t].copy())
            sent[i % len(requests)] += 1
            i += 1
            if time.perf_counter() - t0 >= ctx.seconds:
                break
    run.window_s = time.perf_counter() - t0
    harness.note("window_compiles",
                 harness.CompileMeter.since(before, ctx.meter.snapshot()))
    run.requests = i
    run.searches = i * requests.shape[1]
    run.attempted = run.searches
    run.device = harness.device_info(ctx.devices)
    return sent


def traversed_edges(ref, requests: np.ndarray, sent: np.ndarray) -> int:
    """The Graph500 traversed edges of the searches a closed window
    completed: each key's count (``Reference.traversed_edges``, the edges
    of its component, for BFS and SSSP alike) times how often its request
    was sent."""
    per_request = ref.traversed_edges(requests.reshape(-1)).reshape(
        requests.shape).sum(axis=1)
    return int((per_request * sent).sum())


class Stages:
    """Seconds of each set-up stage, from the run's start."""

    def __init__(self, t_start: float):
        self.t = t_start
        self.seconds: dict[str, float] = {}

    def mark(self, stage: str) -> None:
        now = time.perf_counter()
        self.seconds[stage] = now - self.t
        self.t = now


def build_graph(ctx: Context):
    """``(num_vertices, src, dst)`` of the configuration's graph, every
    undirected edge stored both ways."""
    n, u, v = ctx.make_edges(ctx.config["graph"])
    return n, np.concatenate([u, v]), np.concatenate([v, u])


def layout_shapes(rg) -> dict:
    """The layout sizes the kernel metrics select their calls by."""
    return {"net_size": int(rg.net_size)}


def engine_arms(eng) -> dict:
    """Which arms the engine chose at init, as it reports them."""
    probe = eng.applier_probe or {}
    return {
        "applier": eng.applier,
        "applier_probe": {k: probe.get(k) for k in (
            "selected", "selection_basis", "pallas_net_apply_seconds",
            "xla_net_apply_seconds")},
        "expansion": eng.expansion,
        "expansion_basis": getattr(eng, "expansion_basis", None),
        "direction": eng.direction.mode,
        "packed": bool(eng.packed),
        "phase_selection": eng.phase_selection,
    }
