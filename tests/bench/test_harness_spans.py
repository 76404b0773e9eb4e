"""The program-span reduction (``benchmark/spans.py``) and the seven
metrics that read it, on hand-made profiles in the style of
``test_harness_trace.profile()`` and on the recorded v5e trace, whose
program has neither the spans nor the kernel names."""

from __future__ import annotations

import os
from types import SimpleNamespace as NS

import pytest

from benchmark import harness
from benchmark import spans as sp
from benchmark import trace as tr
from benchmark.runs import Run

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "v5e_small.xplane.pb")


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


LOOP = "%while.2 = (s32[], u32[16,128]{1,0}) while((s32[], u32[16,128]{1,0}) %t)"
COND = ("%conditional.{} = (s32[], u32[16,128]{{1,0}}) conditional(pred[] %p, "
        "(s32[], u32[16,128]{{1,0}}) %t), true_computation=%dense, "
        "false_computation=%sparse")
NET = ('%relay_net_local.7 = u32[16,128]{1,0:T(8,128)} custom-call('
       'u32[16,128]{1,0:T(8,128)} %x, u32[64,128]{1,0:T(8,128)} %m), '
       'custom_call_target="tpu_custom_call"')
VMAP_NET = NET.replace("relay_net_local.7", "vmap_relay_net_local_.15")
OLD_NET = NET.replace("relay_net_local.7", "branch_1_fun.25")
GATHER = "%fusion.349 = s32[65536]{0} fusion(s32[65536]{0} %a), kind=kLoop"

SINGLE_METRICS = ["relabel_ms_per_search.single", "fetch_ms_per_search.single",
                  "sparse_superstep_ms_per_search.single",
                  "dense_superstep_ms_per_search.single",
                  "device_idle_unattributed.traversal"]
MULTI_METRICS = ["relabel_ms_per_batch.multi64", "fetch_ms_per_batch.multi64",
                 "device_idle_unattributed.traversal"]


def profile(net=NET, program_spans=True, device=True):
    """Window 0..1000 ns.  Device: a loop over 100-600 holding a dense
    superstep (conditional 100-300 with a net pass 150-250 and an inner
    conditional 260-290) and a sparse one (conditional 350-550 with a
    gather); a sparse superstep over 950-1100, cut at the window's end.
    Busy 100-600 and 950-1000, so idle 0-100 and 600-950 (450 ns).
    Host: two searches, 50-700 and 720-1000, each with its launch, wait,
    fetch and relabel; no program span over 0-50 and 700-720."""
    ops = [ev(LOOP, 100, 500), ev(COND.format(1), 100, 200), ev(net, 150, 100),
           ev(COND.format(2), 260, 30), ev(COND.format(3), 350, 200),
           ev(GATHER, 360, 180), ev(COND.format(4), 950, 150),
           ev(GATHER, 960, 100)]
    host = [ev(tr.WINDOW, 0, 1000), ev("bench.request", 40, 670),
            ev("PjitFunction(fused)", 60, 5)]
    if program_spans:
        host += [ev("relay.search", 50, 650), ev("relay.launch", 55, 20),
                 ev("relay.device_wait", 75, 525), ev("relay.fetch", 600, 40),
                 ev("relay.relabel", 640, 60),
                 ev("relay.search", 720, 400), ev("relay.launch", 725, 10),
                 ev("relay.device_wait", 735, 265), ev("relay.fetch", 1000, 50)]
    planes = [NS(name="/host:CPU", lines=[NS(name="python", events=host)])]
    if device:
        planes.append(NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=ops)]))
    return NS(planes=planes)


def run_of(trace, batch=1, requests=2):
    return Run(kind="closed_search", searches=requests * batch,
               requests=requests, trace=trace, facts={"batch": batch})


def read_all(run, names):
    return harness.read_metrics([{"name": n, "unit": "x"} for n in names], run)


def test_span_seconds_clip_to_the_window():
    t = tr.from_profile(profile())
    assert sp.span_seconds(t, "relay.relabel") == pytest.approx(60e-9)
    # 600-640, and the second fetch's 1000-1050 falls outside
    assert sp.span_seconds(t, "relay.fetch") == pytest.approx(40e-9)
    assert sp.span_seconds(t, "relay.rerun_unpacked") is None


def test_idle_time_without_a_program_span():
    idle, unattributed = sp.idle_seconds(tr.from_profile(profile()))
    assert idle == pytest.approx(450e-9)
    assert unattributed == pytest.approx(70e-9)  # 0-50 and 700-720


@pytest.mark.parametrize("net", [NET, VMAP_NET])
def test_superstep_split_by_the_net_kernel(net):
    """Outermost conditionals only (the inner one at 260-290 is part of
    the dense superstep), each classified by whether a ``relay_net_*``
    kernel runs inside it, under its own name or wrapped by ``vmap``;
    the last one is cut at the window's end."""
    dense, sparse = sp.superstep_seconds(tr.from_profile(profile(net=net)))
    assert dense == pytest.approx(200e-9)
    assert sparse == pytest.approx(200e-9 + 50e-9)


def test_single_cell_readers():
    got = read_all(run_of(tr.from_profile(profile())), SINGLE_METRICS)
    assert {k: v["value"] for k, v in got.items()} == pytest.approx({
        "relabel_ms_per_search.single": 60e-9 * 1e3 / 2,
        "fetch_ms_per_search.single": 40e-9 * 1e3 / 2,
        "sparse_superstep_ms_per_search.single": 250e-9 * 1e3 / 2,
        "dense_superstep_ms_per_search.single": 200e-9 * 1e3 / 2,
        "device_idle_unattributed.traversal": 100.0 * 70 / 450,
    })
    # The batch metrics belong to the batch cell.
    assert read_all(run_of(tr.from_profile(profile())),
                    ["relabel_ms_per_batch.multi64"]) == {}


def test_batch_cell_readers():
    got = read_all(run_of(tr.from_profile(profile()), batch=64), MULTI_METRICS
                   + ["dense_superstep_ms_per_search.single"])
    assert {k: v["value"] for k, v in got.items()} == pytest.approx({
        "relabel_ms_per_batch.multi64": 60e-9 * 1e3 / 2,
        "fetch_ms_per_batch.multi64": 40e-9 * 1e3 / 2,
        "device_idle_unattributed.traversal": 100.0 * 70 / 450,
    })


@pytest.mark.parametrize("case", ["untraced", "no_program_spans",
                                  "unnamed_kernels", "no_device"])
def test_readers_find_nothing_to_read(case):
    """A program without the spans or the kernel names (the commit before
    them), an untraced run and a run off the chip leave each metric out
    that has nothing to read; none raises."""
    names = sorted(set(SINGLE_METRICS + MULTI_METRICS))
    if case == "untraced":
        assert read_all(run_of(None), names) == {}
        return
    p = {"no_program_spans": profile(program_spans=False),
         "unnamed_kernels": profile(net=OLD_NET),
         "no_device": profile(device=False)}[case]
    got = read_all(run_of(tr.from_profile(p)), names)
    if case == "no_program_spans":
        assert set(got) == {"sparse_superstep_ms_per_search.single",
                            "dense_superstep_ms_per_search.single"}
    elif case == "unnamed_kernels":
        assert set(got) == {"relabel_ms_per_search.single",
                            "fetch_ms_per_search.single",
                            "device_idle_unattributed.traversal"}
    else:
        assert set(got) == {"relabel_ms_per_search.single",
                            "fetch_ms_per_search.single"}


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="fixture trace missing")
def test_recorded_v5e_trace_has_nothing_to_read():
    from jax.profiler import ProfileData

    t = tr.from_profile(ProfileData.from_file(FIXTURE))
    assert sp.idle_seconds(t) is None and sp.superstep_seconds(t) is None
    names = sorted(set(SINGLE_METRICS + MULTI_METRICS))
    assert read_all(run_of(t), names) == {}


@pytest.mark.parametrize("kind", ["closed_search", "fixture_plain"])
def test_driver_neutral_readers(kind):
    """``teps`` and the device idle share read what they read before for
    a ``closed_search`` run, and the same for any other driver's run."""
    t = tr.from_profile(profile())
    run = Run(kind=kind, searches=2, requests=2, traversed_edges=3000,
              window_s=1.5, trace=t, facts={"batch": 1})
    got = read_all(run, ["teps", "device_idle_share.traversal"])
    assert got["teps"]["value"] == 3000 / 1.5
    assert got["device_idle_share.traversal"]["value"] == t.idle_share == \
        pytest.approx(45.0)


def test_searches_without_counted_edges_read_no_teps():
    """A run that completed searches but counted no traversed edges (an
    open loop of bursts, say) has no ``teps`` to read."""
    run = Run(kind="fixture_bursts", searches=16, requests=16, window_s=0.6)
    assert read_all(run, ["teps"]) == {}
