"""The trace reduction: busy and idle time, kernel time by name pattern,
the breakdown, on a hand-made profile and on a small trace recorded on a
TPU v5e (``fixtures/v5e_small.xplane.pb``)."""

from __future__ import annotations

import os
from types import SimpleNamespace as NS

import pytest

from benchmark import roofline
from benchmark import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "v5e_small.xplane.pb")


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


NET = ('%net.1 = u32[16,128]{1,0:T(8,128)} custom-call(u32[16,128]{1,0:T(8,128)S(1)} '
       '%x, u32[64,128]{1,0:T(8,128)} %m), custom_call_target="tpu_custom_call"')
LOOP = "%while.2 = (s32[], u32[16,128]{1,0}) while((s32[], u32[16,128]{1,0}) %t)"
ROWMIN = "%fusion.3 = s32[512]{0:T(512)} fusion(s32[512]{0} %a), kind=kLoop"


def profile():
    """Window 0..1000 ns.  Device ops: a loop over 100-400 holding a net
    pass (100-300) and a row-min (300-400); net passes over 600-700 and
    950-1100 (cut at the window's end).  Host events on the window's
    thread: a request over 0-500, another over 500-1000, and a wait
    inside the second over 700-950."""
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_fused", 0, 1000)]),
        NS(name="XLA Ops", events=[ev(LOOP, 100, 300), ev(NET, 100, 200),
                                   ev(ROWMIN, 300, 100), ev(NET, 600, 100),
                                   ev(NET, 950, 150)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(tr.WINDOW, 0, 1000), ev("bench.request", 0, 500),
        ev("bench.request", 500, 500), ev("bench.wait", 700, 250),
        ev("PjitFunction(f)", 10, 5)])])
    other = NS(name="/device:TPU_NON_CORE:0", lines=[
        NS(name="XLA Ops", events=[ev("noise", 0, 1000)])])
    return NS(planes=[host, device, other])


def test_busy_idle_and_kernel_time():
    t = tr.from_profile(profile())
    assert t.window_s == pytest.approx(1000e-9)
    # union: 100-400, 600-700, 950-1000 -> 450 ns busy
    assert t.busy_s == pytest.approx(450e-9)
    assert t.idle_share == pytest.approx(55.0)
    net = t.op_events(lambda hlo: hlo.startswith("%net."))
    assert [d for _, d in net] == pytest.approx([200e-9, 100e-9, 50e-9])
    assert t.op_events(lambda hlo: False) == []


def test_breakdown():
    b = tr.from_profile(profile()).breakdown()
    ops = dict(b["device_ops"])
    # Exclusive time: the loop's body ops are not counted twice.
    assert ops == pytest.approx({
        "net.1 u32[16,128] custom-call tpu_custom_call": 350e-9,
        "fusion.3 s32[512] fusion": 100e-9,
        "while.2 (s32[], u32[16,128]) while": 0.0})
    assert b["device_ops"][0][0].startswith("net.1")
    gaps = dict(b["idle_gaps"])
    # 0-100 and 400-500 under the first request, 500-600 under the
    # second, 700-950 under the wait
    assert gaps["bench.request"] == pytest.approx(300e-9)
    assert gaps["bench.wait"] == pytest.approx(250e-9)


def test_no_device_plane_reads_nothing():
    p = profile()
    p.planes = [p.planes[0]]
    t = tr.from_profile(p)
    assert t.busy_s == 0.0 and t.idle_share is None
    assert t.breakdown()["device_ops"] == []


def test_roofline_of_a_pallas_call():
    """HBM bytes: the result (16*128 words) and the mask operand (64*128
    words); the operand marked S(1) lives in VMEM and is not counted."""
    assert roofline.pallas_call(NET) == (16 * 128, (16 + 64) * 128 * 4)
    assert roofline.pallas_call(ROWMIN) is None
    share = roofline.roofline_share([(NET, 1e-6)], peak_bytes_per_s=1e9)
    assert share == pytest.approx(100.0 * (80 * 128 * 4 / 1e9) / 1e-6)


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="fixture trace missing")
def test_recorded_v5e_trace():
    """A trace recorded on a TPU v5e: a Pallas kernel and a fused matmul
    three times, each followed by a 20 ms host wait."""
    from jax.profiler import ProfileData

    t = tr.from_profile(ProfileData.from_file(FIXTURE))
    assert list(t.ops) == ["/device:TPU:0"]
    assert 0.06 < t.window_s < 0.2
    assert 0 < t.busy_s < 0.01 * t.window_s
    assert t.idle_share > 99
    kernel = t.op_events(lambda hlo: "tpu_custom_call" in hlo)
    assert len(kernel) >= 2  # the profiler may miss the first call
    assert roofline.pallas_call(kernel[0][0]) == (1024 * 1024, 2 * 4 * 1024 * 1024)
    b = t.breakdown()
    assert b["device_ops"][0][0].startswith("fixture_double")
    assert b["idle_gaps"][0][0].endswith("sleep")
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(
        t.window_s - t.busy_s, rel=1e-6)


def test_a_trace_without_the_window_is_refused():
    p = profile()
    p.planes[0].lines[0].events = p.planes[0].lines[0].events[1:]
    with pytest.raises(ValueError):
        tr.from_profile(p)
