"""Closed loop of single-source shortest-path searches, Graph500 kernel 3:
one caller sends one key at a time through the program's SSSP entry
point, ``bfs_tpu.algo.sssp.sssp_device``, against the weighted edges put
on the device once, each request after the last returns.  The graph
maker's ``weighted_edges`` gives the edges and their float32 weights;
the program receives them as a ``Graph`` with stored weights.  The
requests come from ``traffic.closed_requests``, the window from
``runs.closed_window``; after the window ``runs.traversed_edges`` counts
the Graph500 edges, and a seeded sample of ``check_trees`` trees is
validated against ``reference_sssp.py``, the first two recomputed whole.

Traffic parameters: ``keys``, ``batch`` (1), ``structure_seed`` (see
``traffic.py``), ``warmup_requests`` and ``check_trees``.
"""

from __future__ import annotations

import gc

import numpy as np

from benchmark import harness, traffic
from benchmark.reference import Reference
from benchmark.reference_sssp import ReferenceSssp, bfloat16, check_trees
from benchmark.runs import (Context, Reservoir, Run, Stages, closed_window,
                            traversed_edges)

#: The program's counters that the window reads.
COUNTERS = ("sssp_searches", "sssp_supersteps")


class ProgramEngine:
    """The program: the graph's edges and weights on the device once
    (``build_device_graph``), one ``sssp_device`` call per search."""

    def __init__(self, graph):
        import jax.numpy as jnp

        from bfs_tpu.graph.csr import build_device_graph

        dg = build_device_graph(graph)
        self.num_vertices = dg.num_vertices
        self.src = jnp.asarray(dg.src)
        self.dst = jnp.asarray(dg.dst)
        self.weight = jnp.asarray(dg.weight)

    def search(self, key: int):
        from bfs_tpu.algo.sssp import sssp_device

        res = sssp_device(self.src, self.dst, self.num_vertices, key,
                          weight=self.weight)
        return res.dist, res.parent


def drive(ctx: Context, make_engine=None) -> Run:
    """One run; ``make_engine(num_vertices, src, dst, weight)`` puts
    another engine in the program's place (the control)."""
    from bfs_tpu.graph.csr import Graph
    from bfs_tpu.obs.registry import get_registry

    params = ctx.params
    if int(params["batch"]) != 1:
        raise harness.BenchError("closed_sssp sends one key per request")
    run = Run(kind="closed_sssp")
    stages = Stages(ctx.t_start)
    maker = harness.load_plugin("graphs", ctx.config["graph"]["generator"])
    n, u, v, w = maker.weighted_edges(ctx.config["graph"])
    src, dst = np.concatenate([u, v]), np.concatenate([v, u])
    weight = np.concatenate([w, w])
    graph = Graph(n, src, dst, weight=weight)
    stages.mark("start_and_graph")
    eng = (make_engine(n, src, dst, weight) if make_engine
           else ProgramEngine(graph))
    del graph
    stages.mark("engine")
    requests = traffic.closed_requests(
        params, np.bincount(src, minlength=n), ctx.seed)
    run.facts["batch"] = 1
    run.facts["num_vertices"] = n

    def ask(sources):
        dist, parent = eng.search(int(sources[0]))
        return dist[None, :], parent[None, :]

    for k in range(int(params["warmup_requests"])):
        ask(requests[k % len(requests)])
    stages.mark("warmup")
    run.facts["setup_stages"] = stages.seconds

    sample = Reservoir(params["check_trees"],
                       traffic.rng(ctx.seed, traffic.SAMPLE))
    registry = get_registry()
    before = [registry.count(c) for c in COUNTERS]
    sent = closed_window(ctx, run, requests, ask, sample)
    for c, b in zip(COUNTERS, before):
        run.facts[c] = registry.count(c) - b
    del ask, eng
    gc.collect()

    run.traversed_edges = traversed_edges(Reference(n, src, dst), requests,
                                          sent)
    run.checks = check_trees(ReferenceSssp(n, src, dst, weight), sample.items)
    return run


class RoundedEngine:
    """The control: the reference in the program's place, each distance
    rounded to bfloat16 and back, as a program that computed in the next
    lower precision than float32 would answer."""

    def __init__(self, ref: ReferenceSssp):
        self.ref = ref

    def search(self, key: int):
        dist, parent = self.ref.search(key)
        return bfloat16(dist), parent


def control() -> dict:
    """The ``drive`` hook that puts :class:`RoundedEngine` in the
    program's place."""
    def make_engine(n, src, dst, weight):
        return RoundedEngine(ReferenceSssp(n, src, dst, weight))
    return {"make_engine": make_engine}
