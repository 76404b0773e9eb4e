"""Closed loop of searches: one caller drives one ``RelayEngine``, each
request after the last returns, ``RelayEngine.run(root)`` for a batch of
1 and ``run_multi(sources)`` otherwise.  The requests come from
``traffic.closed_requests``; the window's searches, a seeded sample of
``check_trees`` of them, are checked whole against the reference.

Traffic parameters: ``keys``, ``batch``, ``structure_seed`` (see
``traffic.py``), ``warmup_requests`` and ``check_trees``.
"""

from __future__ import annotations

import gc
from types import SimpleNamespace

import numpy as np

from benchmark import harness, traffic
from benchmark.reference import UNREACHED, Reference
from benchmark.runs import (Context, Reservoir, Run, Stages, build_graph,
                            closed_window, engine_arms, layout_shapes,
                            traversed_edges)


def drive(ctx: Context, make_engine=None) -> Run:
    """One run; ``make_engine(relay_graph, (num_vertices, src, dst))``
    puts another engine in the program's place (tests, the control)."""
    from bfs_tpu.cache.layout import LayoutCache, load_or_build_relay
    from bfs_tpu.graph.csr import Graph
    from bfs_tpu.models.bfs import RelayEngine

    params = ctx.params
    run = Run(kind="closed_search")
    stages = Stages(ctx.t_start)
    n, src, dst = build_graph(ctx)
    stages.mark("start_and_graph")
    rg, info = load_or_build_relay(Graph(n, src, dst), cache=LayoutCache())
    stages.mark("layout")
    harness.note("layout", {"cache": info.get("cache"),
                            "builder": info.get("builder"),
                            "load_s": info.get("load_seconds"),
                            "build_s": info.get("build_seconds")})
    run.layout = layout_shapes(rg)
    eng = make_engine(rg, (n, src, dst)) if make_engine else RelayEngine(rg)
    stages.mark("engine")
    if hasattr(eng, "applier_probe"):
        harness.note("arms", engine_arms(eng))
    degree = np.bincount(src, minlength=n)
    requests = traffic.closed_requests(params, degree, ctx.seed)
    run.facts["batch"] = int(requests.shape[1])
    call = eng.run if requests.shape[1] == 1 else eng.run_multi

    def ask(sources):
        if requests.shape[1] == 1:
            res = call(int(sources[0]))
            return res.dist[None, :], res.parent[None, :]
        res = call(sources)
        return res.dist, res.parent

    for k in range(int(params["warmup_requests"])):
        ask(requests[k % len(requests)])
    stages.mark("warmup")
    run.facts["setup_stages"] = stages.seconds

    sample = Reservoir(params["check_trees"],
                       traffic.rng(ctx.seed, traffic.SAMPLE))
    sent = closed_window(ctx, run, requests, ask, sample)
    del ask, call, eng, rg
    gc.collect()

    ref = Reference(n, src, dst)
    run.traversed_edges = traversed_edges(ref, requests, sent)
    run.checks = check_trees(ref, sample.items)
    return run


def check_trees(ref: Reference, items: list) -> dict:
    """Distances and parent trees of the sampled searches against the
    reference."""
    roots = sorted({root for root, _, _ in items})
    want = dict(zip(roots, ref.distances(roots)))
    wrong = bad = 0
    for root, dist, parent in items:
        w, b = ref.tree_faults(root, want[root], dist, parent)
        wrong += w
        bad += b
    return {
        "wrong_distances": {"value": wrong, "limit": 0},
        "bad_parents": {"value": bad, "limit": 0},
        "unchecked": {"value": 0 if items else 1, "limit": 0},
    }


class ShortEngine:
    """The control: the reference in the engine's place, each search
    stopped one level short of its last level, as a level cap set too
    low would stop it (the program's packed state carries such a cap);
    the farthest vertices read as unreached."""

    def __init__(self, ref: Reference):
        self.ref = ref

    def _one(self, root: int, dist: np.ndarray):
        dist = dist.copy()
        last = dist[dist != UNREACHED].max()
        if last > 0:
            dist[dist == last] = UNREACHED
        return dist, self.ref.parents(dist, root)

    def run(self, root: int):
        dist, parent = self._one(root, self.ref.distances([root])[0])
        return SimpleNamespace(dist=dist, parent=parent)

    def run_multi(self, sources):
        rows = [self._one(int(s), d) for s, d in
                zip(sources, self.ref.distances(sources))]
        return SimpleNamespace(dist=np.stack([r[0] for r in rows]),
                               parent=np.stack([r[1] for r in rows]))


def control() -> dict:
    """The ``drive`` hook that puts :class:`ShortEngine` in the
    program's place."""
    def make_engine(rg, graph):
        return ShortEngine(Reference(*graph))
    return {"make_engine": make_engine}
