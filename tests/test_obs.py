"""Tests for bfs_tpu.obs: spans (nesting, Chrome-trace export, journal
round-trip through a resumed run, SIGTERM-style flush), device superstep
telemetry (exit-only pulls asserted with a jax.device_get spy, level
curves vs the oracle across engines), the MetricsRegistry snapshot with
both exporter formats, the eviction counter satellite, and the
percentile / zero-query ServeMetrics edge cases."""

from __future__ import annotations

import json

import numpy as np
import pytest

from bfs_tpu.graph.generators import path_graph, rmat_graph
from bfs_tpu.obs import registry as obs_registry
from bfs_tpu.obs import spans as obs_spans
from bfs_tpu.obs.spans import (
    chrome_trace,
    flush_open_spans,
    instant,
    journal_spans,
    snapshot_events,
    span,
    span_report,
    stitch_journal_trace,
)
from bfs_tpu.obs.telemetry import TEL_SLOTS, level_curve, render_curve_ascii
from bfs_tpu.oracle.bfs import queue_bfs
from bfs_tpu.utils.metrics import ServeMetrics, percentile

INF = np.iinfo(np.int32).max


@pytest.fixture(autouse=True)
def _clean_spans():
    obs_spans.drain_events()
    yield
    obs_spans.drain_events()


@pytest.fixture(scope="module")
def small_graph():
    return rmat_graph(9, 8, seed=7)


def oracle_curve(graph, source):
    dist, _ = queue_bfs(graph, source)
    reached = dist != INF
    return int(reached.sum()), [int(x) for x in np.bincount(dist[reached])]


# ---------------------------------------------------------------------------
# Spans.
# ---------------------------------------------------------------------------

def test_span_nesting_containment():
    with span("outer", phase="x"):
        with span("inner"):
            pass
    evs = [e for e in snapshot_events() if e["ph"] == "X"]
    assert [e["name"] for e in evs] == ["inner", "outer"]  # close order
    inner, outer = evs
    # Perfetto infers nesting from containment on one tid: outer must
    # envelop inner in both start and end.
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert inner["tid"] == outer["tid"]
    assert outer["args"] == {"phase": "x"}


def test_span_decorator_and_report():
    @span("unit.work")
    def work(x):
        return x + 1

    assert work(1) == 2
    assert work(2) == 3
    rep = span_report()
    assert rep["unit.work"]["count"] == 2
    assert rep["unit.work"]["total_s"] > 0


def test_span_disabled_by_env(monkeypatch):
    monkeypatch.setenv("BFS_TPU_SPANS", "0")
    with span("invisible"):
        instant("also.invisible")
    assert snapshot_events() == []


def test_span_error_annotated():
    with pytest.raises(ValueError):
        with span("fails"):
            raise ValueError("boom")
    (ev,) = snapshot_events()
    assert ev["args"]["error"] == "ValueError"


def test_flush_open_spans_sigterm_shape():
    """The SIGTERM path: a still-open span gets its real duration so far
    plus the flush marker — an interrupted run leaves a usable trace."""
    sp = span("bench.repeat", i=1)
    sp.__enter__()
    n = flush_open_spans("signal:SIGTERM")
    assert n == 1
    (ev,) = snapshot_events()
    assert ev["name"] == "bench.repeat"
    assert ev["args"]["flushed"] == "signal:SIGTERM"
    assert ev["dur"] >= 1
    # Exiting after the flush must not double-emit.
    sp.__exit__(None, None, None)
    assert len(snapshot_events()) == 1


def test_chrome_trace_is_perfetto_loadable_shape(tmp_path):
    with span("a"):
        instant("marker", graph="g")
    doc = chrome_trace()
    # Perfetto's JSON importer wants traceEvents with name/ph/ts/pid/tid;
    # complete events carry dur.  Round-trip through real JSON.
    doc = json.loads(json.dumps(doc))
    assert isinstance(doc["traceEvents"], list) and len(doc["traceEvents"]) == 2
    for ev in doc["traceEvents"]:
        for k in ("name", "ph", "ts", "pid", "tid"):
            assert k in ev, ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 1
    out = obs_spans.export_chrome_trace(str(tmp_path / "t.json"))
    assert json.load(open(out))["traceEvents"]


def test_journal_roundtrip_through_resumed_run(tmp_path):
    """A killed-and-resumed bench journals one spans:<k> record per
    process generation; the stitched trace holds every generation's
    events in order."""
    from bfs_tpu.resilience.journal import RunJournal

    cfg = {"bench": "t", "scale": 4}
    path = str(tmp_path / "run.jsonl")

    jr = RunJournal(path, cfg)
    with span("gen0.phase"):
        pass
    assert journal_spans(jr) == "spans:0"
    jr.close()

    # "Resume": same config reopens the same journal file.
    jr2 = RunJournal(path, cfg)
    assert "spans:0" in jr2.resumed_phases
    with span("gen1.phase"):
        pass
    assert journal_spans(jr2) == "spans:1"
    jr2.close()

    doc = stitch_journal_trace(path)
    names = [e["name"] for e in doc["traceEvents"]]
    assert names == ["gen0.phase", "gen1.phase"]
    # Journaling drained the buffer: nothing double-counts.
    assert snapshot_events() == []
    # Empty buffer -> no-op, no empty record.
    jr3 = RunJournal(path, cfg)
    assert journal_spans(jr3) is None
    jr3.close()


def _profiled(fn, trace_dir) -> list:
    """``(name, start_ns, end_ns)`` of the profiler's host events on the
    thread that ran ``fn`` under a CPU ``jax.profiler`` trace."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("test.window"):
            fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                      for ev in line.events]
            if any(name == "test.window" for name, _, _ in events):
                return events
    raise AssertionError("no host line of the trace holds the window")


def _nesting(events, prefix: str) -> list:
    """``[(name, depth), ...]`` of the events named ``prefix*`` in start
    order, depth counted by containment among them."""
    picked = sorted((e for e in events if e[0].startswith(prefix)),
                    key=lambda e: (e[1], -e[2]))
    out, stack = [], []
    for name, s, e in picked:
        while stack and stack[-1] <= s:
            stack.pop()
        out.append((name, len(stack)))
        stack.append(e)
    return out


def test_span_opens_a_profiler_annotation(tmp_path):
    """A span is also a profiler annotation under its bare name, nested
    on the thread's line like the span itself; attributes stay in the
    Chrome export."""
    def work():
        with span("unit.outer", rows=3):
            with span("unit.inner"):
                pass

    events = _profiled(work, tmp_path / "trace")
    assert _nesting(events, "unit.") == [("unit.outer", 0), ("unit.inner", 1)]
    assert [e["name"] for e in snapshot_events()] == ["unit.inner", "unit.outer"]


def test_exported_events_carry_id_and_parent():
    with span("outer"):
        with span("inner"):
            instant("mark")
        with span("sibling"):
            pass
    with span("top"):
        pass
    ev = {e["name"]: e for e in snapshot_events()}
    ids = [e["id"] for e in ev.values()]
    assert len(set(ids)) == len(ids)
    assert ev["outer"]["parent"] is None and ev["top"]["parent"] is None
    assert ev["inner"]["parent"] == ev["outer"]["id"]
    assert ev["sibling"]["parent"] == ev["outer"]["id"]
    assert ev["mark"]["parent"] == ev["inner"]["id"]
    # Round-trips through the Chrome export.
    doc = json.loads(json.dumps(chrome_trace()))
    assert {e["name"]: e["parent"] for e in doc["traceEvents"]}["inner"] == \
        ev["outer"]["id"]


def test_flushed_span_keeps_its_parent():
    outer = span("outer")
    outer.__enter__()
    with span("inner"):
        pass
    flush_open_spans("signal:SIGTERM")
    outer.__exit__(None, None, None)
    ev = {e["name"]: e for e in snapshot_events()}
    assert ev["outer"]["args"]["flushed"] == "signal:SIGTERM"
    assert ev["outer"]["parent"] is None
    assert ev["inner"]["parent"] == ev["outer"]["id"]


def test_spans_off_emit_into_neither_sink(monkeypatch, tmp_path):
    monkeypatch.setenv("BFS_TPU_SPANS", "0")

    def work():
        with span("unit.invisible"):
            instant("unit.also_invisible")

    events = _profiled(work, tmp_path / "trace")
    assert not [e for e in events if e[0].startswith("unit.")]
    assert snapshot_events() == []


#: The engine's spans of one search that re-runs unpacked, in start order
#: with their depth below the search's own span.
SEARCH_SPANS = [
    ("relay.launch", 1), ("relay.device_wait", 1), ("relay.fetch", 1),
    ("relay.rerun_unpacked", 1),
    ("relay.launch", 2), ("relay.device_wait", 2), ("relay.fetch", 2),
    ("relay.relabel", 1),
]


def test_engine_spans_and_counters(tmp_path):
    """``RelayEngine.run`` and ``run_multi`` on a path deeper than the
    packed level field: every boundary appears nested in order on the
    profiler's line, and the counters move by one search (a batch by its
    trees), one re-run each, and the bytes of every fetch."""
    from bfs_tpu.models.bfs import RelayEngine

    g = path_graph(70)
    eng = RelayEngine(g)
    assert eng.packed
    sources = np.array([0, 5, 9], np.int32)
    eng.run(0)  # compile outside the trace
    eng.run_multi(sources)
    obs_spans.drain_events()
    reg = obs_registry.get_registry()
    names = ("relay_searches", "relay_unpacked_reruns", "relay_fetch_bytes",
             "relay_relabel_parallel")
    before = {k: reg.count(k) for k in names}
    out = {}

    def work():
        out["single"] = eng.run(0)
        out["multi"] = eng.run_multi(sources)

    events = _profiled(work, tmp_path / "trace")
    assert out["single"].dist.tolist() == list(range(70))
    assert out["multi"].dist[1].tolist() == [abs(v - 5) for v in range(70)]
    assert _nesting(events, "relay.") == (
        [("relay.search", 0)] + SEARCH_SPANS
        + [("relay.batch", 0)] + SEARCH_SPANS)
    moved = {k: reg.count(k) - before[k] for k in names}
    fetched = [e for e in snapshot_events() if e["name"] == "relay.fetch"]
    assert len(fetched) == 4
    # Each fetch carries at least the dist and parent words of its trees.
    for e, trees in zip(fetched, (1, 1, 3, 3)):
        assert e["args"]["bytes"] >= trees * 2 * 4 * 70
    # 70 and 210 values relabel inline: one worker, no parallel count.
    assert moved == {"relay_searches": 1 + 3, "relay_unpacked_reruns": 2,
                     "relay_fetch_bytes": sum(e["args"]["bytes"] for e in fetched),
                     "relay_relabel_parallel": 0}
    assert [e["args"] for e in snapshot_events()
            if e["name"] == "relay.relabel"] == [{"workers": 1}] * 2
    ev = {(e["name"], e["id"]): e for e in snapshot_events()}
    by_id = {e["id"]: e["name"] for e in ev.values()}
    for (name, _), e in ev.items():
        if name in ("relay.search", "relay.batch"):
            assert e["parent"] is None
        elif name in ("relay.rerun_unpacked", "relay.relabel"):
            assert by_id[e["parent"]] in ("relay.search", "relay.batch")
    assert [e["args"] for e in snapshot_events()
            if e["name"] == "relay.batch"] == [{"batch": 3}]


def test_relabel_span_carries_workers_and_parallel_counter(monkeypatch):
    """With the relabel split over threads, ``relay.relabel`` names the
    threads it ran on and ``relay_relabel_parallel`` moves by one per
    relabel; at the graph's own size it runs inline and moves by none."""
    import importlib

    from bfs_tpu.models.bfs import RelayEngine

    bfs_mod = importlib.import_module("bfs_tpu.models.bfs")
    eng = RelayEngine(rmat_graph(7, 8, seed=3))
    sources = np.array([0, 5, 9], np.int32)
    reg = obs_registry.get_registry()
    eng.run(0)
    eng.run_multi(sources)
    obs_spans.drain_events()
    before = reg.count("relay_relabel_parallel")
    inline = (eng.run(1), eng.run_multi(sources))
    assert reg.count("relay_relabel_parallel") == before
    monkeypatch.setattr(bfs_mod, "RELABEL_INLINE_MAX", 16)
    monkeypatch.setattr(bfs_mod, "_host_cores", lambda: 3)
    split = (eng.run(1), eng.run_multi(sources))
    assert reg.count("relay_relabel_parallel") == before + 2
    assert [e["args"] for e in snapshot_events()
            if e["name"] == "relay.relabel"] == [{"workers": 1}] * 2 + [
                {"workers": 3}] * 2
    for a, b in zip(inline, split):
        assert a.dist.tobytes() == b.dist.tobytes()
        assert a.parent.tobytes() == b.parent.tobytes()


def test_engine_queues_the_host_copy_before_the_wait(monkeypatch):
    """The copy to the host is queued at dispatch, as ``jax.device_get``
    queues it, so it runs as the program ends: waiting first and copying
    after left the chip idle for the host's wake-up on every search."""
    import jax
    from jax._src.array import ArrayImpl

    from bfs_tpu.models.bfs import RelayEngine

    eng = RelayEngine(rmat_graph(7, 8, seed=3))
    eng.run(0)
    order = []
    real_copy, real_wait = ArrayImpl.copy_to_host_async, jax.block_until_ready

    def copy(self):
        order.append("copy")
        return real_copy(self)

    def wait(x):
        order.append("wait")
        return real_wait(x)

    monkeypatch.setattr(ArrayImpl, "copy_to_host_async", copy)
    monkeypatch.setattr(jax, "block_until_ready", wait)
    eng.run(1)
    first_wait = order.index("wait")
    assert first_wait >= 5 and set(order[:first_wait]) == {"copy"}


def test_event_buffer_bounded(monkeypatch):
    monkeypatch.setattr(obs_spans, "MAX_EVENTS", 3)
    for i in range(5):
        instant(f"m{i}")
    assert len(snapshot_events()) == 3
    assert chrome_trace()["otherData"]["dropped_events"] == 2


# ---------------------------------------------------------------------------
# Device telemetry: exit-only pulls + oracle-checked level curves.
# ---------------------------------------------------------------------------

def test_relay_level_curve_one_device_get(monkeypatch, small_graph):
    """THE tentpole contract: collecting the curve costs exactly ONE
    jax.device_get, of the ~KB accumulators — never the V-sized state."""
    import jax

    from bfs_tpu.models.bfs import RelayEngine

    eng = RelayEngine(small_graph, sparse_hybrid=False)
    reached, hist = oracle_curve(small_graph, 0)

    calls = []
    real = jax.device_get

    def spy(x):
        calls.append(
            sum(int(np.asarray(getattr(l, "size", 1)))
                for l in jax.tree_util.tree_leaves(x))
        )
        return real(x)

    monkeypatch.setattr(jax, "device_get", spy)
    curve = eng.run_level_curve(0)
    monkeypatch.undo()

    assert len(calls) == 1, f"expected ONE pull at loop exit, saw {len(calls)}"
    # fv + fe + direction schedule + (changed, level): the push/pull
    # schedule (ISSUE 7) rides the SAME single loop-exit pull.
    assert calls[0] <= 3 * TEL_SLOTS + 2
    assert curve["reachable"] == reached
    assert curve["occupancy"] == hist


def test_relay_curve_sparse_hybrid_and_edges(small_graph):
    from bfs_tpu.models.bfs import RelayEngine

    eng = RelayEngine(small_graph, sparse_hybrid=True)
    reached, hist = oracle_curve(small_graph, 0)
    curve = eng.run_level_curve(0)
    assert curve["occupancy"] == hist
    # Frontier out-edges: level 0 is the source's own degree; every level
    # is non-negative and the array tracks occupancy's length.
    fe = curve["frontier_edges"]
    assert len(fe) == len(hist)
    assert all(e >= 0 for e in fe)
    assert curve["cap"] == 62 and 0 < curve["cap_proximity"] < 1


@pytest.mark.parametrize("engine", ["pull", "push"])
def test_bfs_level_curve_matches_oracle(engine, small_graph):
    from bfs_tpu.models.bfs import bfs_level_curve

    reached, hist = oracle_curve(small_graph, 0)
    curve = bfs_level_curve(small_graph, 0, engine=engine)
    assert curve["reachable"] == reached
    assert curve["occupancy"] == hist
    assert not curve["truncated"]


def test_level_curve_past_packed_cap_unpacked_fallback():
    """Deeper than the 62-level packed cap: the curve must come from the
    unpacked re-run (full depth), not a truncated packed loop."""
    from bfs_tpu.models.bfs import bfs_level_curve

    g = path_graph(100)
    curve = bfs_level_curve(g, 0, engine="pull")
    assert curve["levels"] == 100
    assert curve["reachable"] == 100
    assert curve["occupancy"] == [1] * 100


def test_multi_source_curve_sums_trees(small_graph):
    from bfs_tpu.models.multisource import bfs_multi_level_curve

    sources = [0, 5, 9]
    expected = sum(oracle_curve(small_graph, s)[0] for s in sources)
    curve = bfs_multi_level_curve(small_graph, sources, engine="pull")
    assert curve["reachable"] == expected
    assert curve["occupancy"][0] == len(sources)


def test_level_curve_host_math():
    fv = np.zeros(TEL_SLOTS, np.int32)
    fv[:4] = [1, 10, 100, 3]
    c = level_curve(fv, cap=62, reference_reached=114)
    assert c["occupancy"] == [1, 10, 100, 3]
    assert c["levels"] == 4 and c["peak_level"] == 2
    assert c["occupancy_sum_matches_reference"]
    assert "L  2" in render_curve_ascii(c)
    # Clamped deep levels mark the curve truncated but keep the sum exact.
    fv[TEL_SLOTS - 1] = 7
    c2 = level_curve(fv)
    assert c2["truncated"] and c2["reachable"] == 121
    # Wide (lo16/hi16) batched accumulator reconstructs exact int64 past
    # the int32 range: 3 + 2**17 * 65536 = 2**33 + 3.
    wide = np.zeros((TEL_SLOTS, 2), np.int32)
    wide[0] = [3, 1 << 17]
    c3 = level_curve(wide)
    assert c3["occupancy"] == [(1 << 33) + 3]


def test_multi_curve_wide_acc_consistency(small_graph):
    """The overflow-safe wide accumulator must agree exactly with the
    scalar path on an in-range workload."""
    from bfs_tpu.models.multisource import bfs_multi_level_curve

    c = bfs_multi_level_curve(small_graph, [0, 1], engine="push")
    a, _ = oracle_curve(small_graph, 0)
    b, _ = oracle_curve(small_graph, 1)
    assert c["reachable"] == a + b


# ---------------------------------------------------------------------------
# MetricsRegistry: one snapshot, two exporter formats.
# ---------------------------------------------------------------------------

def test_registry_snapshot_absorbs_all_surfaces():
    from bfs_tpu.analysis.runtime import bump_retrace
    from bfs_tpu.utils.metrics import bump_artifact

    reg = obs_registry.MetricsRegistry()
    reg.counter("graph_evictions", 3)
    sm = ServeMetrics()
    sm.bump("batches", 2)
    obs_registry.get_registry().register_serve(sm)  # global: via ctor too
    reg.register_serve(sm)
    reg.register_serve(sm)  # idempotent
    bump_artifact("layout_cache_hits")
    bump_retrace("test.obs_fn")
    with span("snap.unit"):
        pass

    snap = reg.snapshot(retrace_baseline={"test.obs_fn": 0})
    assert snap["counters"]["graph_evictions"] == 3
    assert snap["artifact_caches"]["layout_cache_hits"] >= 1
    assert snap["retraces"]["test.obs_fn"] >= 1
    assert snap["retrace_drift"]["test.obs_fn"] >= 1
    assert snap["spans"]["snap.unit"]["count"] == 1
    assert [r["counters"]["batches"] for r in snap["serve"]] == [2]
    json.loads(reg.to_json())  # exporter 1: valid JSON


def test_prometheus_exporter_format():
    reg = obs_registry.MetricsRegistry()
    reg.counter("graph_evictions")
    text = reg.to_prometheus()
    lines = [l for l in text.strip().splitlines() if l]
    assert any(l.startswith("# TYPE bfs_tpu_") for l in lines)
    for l in lines:
        if l.startswith("#"):
            continue
        name, value = l.split(" ", 1)
        assert name.startswith("bfs_tpu_")
        assert all(c.isalnum() or c == "_" for c in name)
        float(value)  # every sample parses as a number
    assert "bfs_tpu_counters_graph_evictions 1" in lines


def test_registry_drops_dead_serve_metrics():
    reg = obs_registry.MetricsRegistry()
    sm = ServeMetrics()
    reg.register_serve(sm)
    assert len(reg.snapshot()["serve"]) == 1
    del sm
    import gc

    gc.collect()
    assert reg.snapshot()["serve"] == []


def test_graph_registry_eviction_emits_counter_and_marker(small_graph):
    """Satellite: HBM-budget thrash is visible — an eviction lands both a
    registry counter and an instant trace marker."""
    from bfs_tpu.serve import GraphRegistry

    reg = obs_registry.get_registry()
    before = reg.count("graph_evictions")
    gr = GraphRegistry(device_budget_bytes=1)  # everything evicts everything
    gr.register("a", small_graph)
    gr.register("b", small_graph)
    gr.acquire("a", "pull")
    gr.acquire("b", "pull")  # evicts a
    assert gr.evictions >= 1
    assert reg.count("graph_evictions") > before
    marks = [e for e in snapshot_events()
             if e["ph"] == "i" and e["name"] == "registry.evict"]
    assert marks and marks[0]["args"]["graph"] == "a"
    assert marks[0]["args"]["bytes"] > 0


# ---------------------------------------------------------------------------
# utils.metrics edge cases (satellite).
# ---------------------------------------------------------------------------

def test_percentile_edge_cases():
    assert percentile([], 50) == 0.0
    assert percentile([], 0) == 0.0
    assert percentile([7.0], 0) == 7.0
    assert percentile([7.0], 100) == 7.0
    vals = [4.0, 1.0, 3.0, 2.0]
    assert percentile(vals, 0) == 1.0
    assert percentile(vals, 100) == 4.0
    assert percentile(vals, 50) == pytest.approx(2.5)
    assert percentile(range(101), 99) == pytest.approx(99.0)


def test_serve_metrics_report_zero_queries():
    rep = ServeMetrics().report()
    assert rep["queries"] == 0 and rep["served"] == 0
    assert rep["latency_p50_ms"] == 0.0 and rep["latency_p99_ms"] == 0.0
    assert rep["latency_mean_ms"] == 0.0
    assert rep["batch_size_mean"] == 0.0 and rep["batch_size_max"] == 0
    assert rep["queries_per_sec"] == 0.0
    assert rep["compile_hit_rate"] is None
    assert rep["result_cache_hit_rate"] is None
    assert rep["retries"]["device_retries"] == 0
    json.dumps(rep)  # JSON-ready even with no traffic


# ---------------------------------------------------------------------------
# CLI.
# ---------------------------------------------------------------------------

def test_obs_cli_trace_and_curve(tmp_path):
    from bfs_tpu.obs.__main__ import main as obs_main
    from bfs_tpu.resilience.journal import RunJournal

    path = str(tmp_path / "run.jsonl")
    jr = RunJournal(path, {"bench": "cli"})
    with span("bench.repeat"):
        pass
    journal_spans(jr)
    jr.put("level_curve", {"level_curve": {
        "occupancy": [1, 2], "levels": 2, "reachable": 3,
        "cap": 62, "cap_proximity": 2 / 62,
    }})
    jr.close()

    out = str(tmp_path / "trace.json")
    assert obs_main(["trace", path, "-o", out]) == 0
    doc = json.load(open(out))
    assert [e["name"] for e in doc["traceEvents"]] == ["bench.repeat"]
    assert obs_main(["curve", path]) == 0
    assert obs_main(["snapshot"]) == 0
    assert obs_main(["snapshot", "--prom"]) == 0
