"""Whole runs on the CPU at a tiny size: a sound run is correct, and a run
whose timed path is broken underneath, or has the control in its place,
is not.  The harness's look for a chip is skipped; everything else runs
as on the chip."""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np
import pytest
from harness_fixtures import run_cell, tiny_root  # noqa: F401 (fixtures)

from benchmark import harness
from benchmark.reference import UNREACHED


class Altered:
    """An answer altered where it is produced: one distance off by one."""

    def __init__(self, rg, graph):
        from bfs_tpu.models.bfs import RelayEngine

        self.eng = RelayEngine(rg)

    def run(self, root):
        res = self.eng.run(root)
        dist = res.dist.copy()
        far = np.flatnonzero((dist != UNREACHED) & (dist > 0))[0]
        dist[far] += 1
        return SimpleNamespace(dist=dist, parent=res.parent)


class Unchanged:
    """A step that returns its state unchanged: every search answers with
    the first search's result."""

    def __init__(self, rg, graph):
        from bfs_tpu.models.bfs import RelayEngine

        self.eng = RelayEngine(rg)
        self.first = None

    def run(self, root):
        if self.first is None:
            self.first = self.eng.run(root)
        return self.first


class HalfBatch:
    """Half of the batch left out: the first half's trees stand in for
    the second half's."""

    def __init__(self, rg, graph):
        from bfs_tpu.models.bfs import RelayEngine

        self.eng = RelayEngine(rg)

    def run_multi(self, sources):
        half = self.eng.run_multi(sources[: len(sources) // 2])
        return SimpleNamespace(dist=np.concatenate([half.dist, half.dist]),
                               parent=np.concatenate([half.parent, half.parent]))


BENCH = harness.load_benchmark()
#: Every cell of the benchmark, as BENCHMARK.json lists them.
CELLS = [w["name"] for w in BENCH["workloads"]]
#: The check that each driver's control is written to fail.
CONTROL_FAILS = {"closed_search": "wrong_distances"}


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(run_cell, workload):
    sound = run_cell(workload)
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    # Off the chip a run reports its host-clock end-to-end metrics, each
    # above 0, and nothing else.
    want = {m["name"] for m in harness.cell_metrics(BENCH, workload, False)
            if m["source"] == "host_clock"}
    assert set(sound["metrics"]) == want
    assert all(m["value"] > 0 for m in sound["metrics"].values())


@pytest.mark.parametrize("workload,fault", [
    ("g500-s20.single", Altered),
    ("g500-s20.single", Unchanged),
    ("g500-s20.multi64", HalfBatch),
])
def test_broken_timed_path_is_not_correct(run_cell, workload, fault):
    broken = run_cell(workload, make_engine=fault)
    assert not broken["correct"], fault.__name__


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(run_cell, workload):
    driver = harness.load_cell(BENCH, workload)[2]["driver"]
    swap = harness.load_plugin("drivers", driver).control()
    ctl = run_cell(workload, **swap)
    assert not ctl["correct"]
    if driver in CONTROL_FAILS:
        assert ctl["checks"][CONTROL_FAILS[driver]]["value"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reads_no_device_metric_off_the_chip(run_cell, workload):
    """Off the chip the trace holds no TPU plane: the device metrics find
    nothing to read and are left out, never read as 0 or 100."""
    res = run_cell(workload, trace=1)
    assert res["correct"]
    for name in res["metrics"]:
        assert not name.startswith(("device_", "net_apply"))
    assert res["device"]["busy_s"] == 0.0 and res["device"]["window_s"] > 0
