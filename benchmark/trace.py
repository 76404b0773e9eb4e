"""Reduction of a profiler trace to device metrics.

A traced run wraps its measured window in ``jax.profiler`` tracing and in
a host annotation named ``bench.window``.  :func:`load` reads the
``.xplane.pb`` that the profiler writes with ``jax.profiler.ProfileData``
and keeps three things: each device's operations (HLO text, start, end),
the host events of the thread that opened the window (the benchmark's
``bench.*`` annotations and the Python frames the profiler records), and
the window.  Everything else is computed from those:

- busy time: the union of a device's operation intervals inside the
  window, averaged over the devices that ran anything;
- a kernel's device time: the summed duration of the operations that a
  metric's own selector picks out of their HLO text;
- the breakdown: the device operations that took most time by their own
  (exclusive) time, since a ``while`` or a ``conditional`` contains the
  operations of its body; and the device's idle time inside the window,
  summed by what the host thread was doing in the middle of each gap.

The device planes are the profiler's ``/device:TPU:<n>`` planes, and
their operations are the events of the ``XLA Ops`` line.  An event's name
is its HLO instruction, ``%fusion.8 = s32[65536]{...} fusion(...), ...``.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
from contextlib import contextmanager

import numpy as np

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
_HLO = re.compile(r"^%?(?P<name>[\w.-]+) = (?P<type>.+?) (?P<op>[\w-]+)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(hlo: str) -> str:
    """A short label of one HLO instruction: name, result type without
    its layout, and the operation (with a custom call's target)."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:120]
    rtype = re.sub(r"\{[^{}]*\}", "", m.group("type"))
    op = m.group("op")
    target = _TARGET.search(hlo)
    if target:
        op = f"{op} {target.group(1)}"
    return f"{m.group('name')} {rtype[:60]} {op}"


class Trace:
    """Device operations and host events of one traced window.

    ``ops`` maps a device plane's name to ``(names, start_ns, end_ns)``,
    ``host`` is a list of ``(name, start_ns, end_ns)`` on the thread that
    opened the window, and ``window`` is the window's ``(start_ns,
    end_ns)``, all on the trace's one clock."""

    def __init__(self, ops: dict, host: list, window: tuple):
        self.ops = {
            dev: (list(names), np.asarray(s, np.float64), np.asarray(e, np.float64))
            for dev, (names, s, e) in ops.items()
        }
        self.host = list(host)
        self.window = (float(window[0]), float(window[1]))

    # ---------------------------------------------------------- timing --
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clipped(self, dev: str):
        names, start, end = self.ops[dev]
        lo, hi = self.window
        return names, np.clip(start, lo, hi), np.clip(end, lo, hi)

    def _busy_intervals(self, dev: str) -> list[tuple[float, float]]:
        _, start, end = self._clipped(dev)
        keep = end > start
        merged: list[list[float]] = []
        for s, e in sorted(zip(start[keep].tolist(), end[keep].tolist())):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return [(s, e) for s, e in merged]

    def _active(self) -> list[str]:
        return [dev for dev, (names, _, _) in sorted(self.ops.items()) if names]

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices
        that ran anything (0 when none did)."""
        devs = self._active()
        if not devs:
            return 0.0
        total = sum(e - s for dev in devs for s, e in self._busy_intervals(dev))
        return total * 1e-9 / len(devs)

    @property
    def idle_share(self) -> float | None:
        """Percent of the window in which the device ran nothing; None
        when the trace holds no device operation to read."""
        if not self._active():
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def op_events(self, select) -> list[tuple[str, float]]:
        """``[(hlo, seconds), ...]`` of the operations inside the window
        for which ``select(hlo)`` is true, on every active device."""
        out = []
        for dev in self._active():
            names, start, end = self._clipped(dev)
            for name, s, e in zip(names, start.tolist(), end.tolist()):
                if e > s and select(name):
                    out.append((name, (e - s) * 1e-9))
        return out

    # ------------------------------------------------------- breakdown --
    def top_ops(self, limit: int = 10) -> list[list]:
        """``[[label, seconds], ...]``: exclusive device time by operation,
        most first, inside the window and averaged over active devices."""
        totals: dict[str, float] = {}
        devs = self._active()
        for dev in devs:
            names, start, end = self._clipped(dev)
            order = np.lexsort((-(end - start), start))
            stack: list[tuple[str, float]] = []
            for i in order.tolist():
                s, e = start[i], end[i]
                if e <= s:
                    continue
                while stack and stack[-1][1] <= s:
                    stack.pop()
                label = op_label(names[i])
                if stack:
                    totals[stack[-1][0]] -= e - s
                totals[label] = totals.get(label, 0.0) + (e - s)
                stack.append((label, e))
        n = max(len(devs), 1)
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
        return [[label, float(t) * 1e-9 / n] for label, t in ranked]

    def idle_gaps(self, limit: int = 10) -> list[list]:
        """``[[host activity, seconds], ...]``: the first device's idle
        time inside the window, summed by the innermost event open on the
        window's host thread in the middle of each gap: a benchmark
        annotation or a Python frame, ``file:line function``."""
        devs = self._active()
        lo, hi = self.window
        busy = self._busy_intervals(devs[0]) if devs else []
        gaps, cursor = [], lo
        for s, e in busy:
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if hi > cursor:
            gaps.append((cursor, hi))
        # One thread's events nest, so a sweep over the gaps' midpoints in
        # time order keeps the open events on a stack.
        host = sorted((h for h in self.host if h[0] != WINDOW),
                      key=lambda h: (h[1], -h[2]))
        totals: dict[str, float] = {}
        stack: list[tuple] = []
        j = 0
        for s, e in gaps:
            mid = 0.5 * (s + e)
            while j < len(host) and host[j][1] <= mid:
                while stack and stack[-1][2] <= host[j][1]:
                    stack.pop()
                stack.append(host[j])
                j += 1
            while stack and stack[-1][2] <= mid:
                stack.pop()
            name = stack[-1][0].lstrip("$") if stack else "no host event"
            totals[name] = totals.get(name, 0.0) + (e - s)
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
        return [[name, float(t) * 1e-9] for name, t in ranked]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def from_profile(profile) -> Trace:
    """A :class:`Trace` from a ``jax.profiler.ProfileData``."""
    ops, lines, window = {}, [], None
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            names, start, end = [], [], []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    names.append(ev.name)
                    start.append(ev.start_ns)
                    end.append(ev.start_ns + ev.duration_ns)
            ops[plane.name] = (names, start, end)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events]
                for ev in events:
                    if ev[0] == WINDOW:
                        window = ev[1:]
                        lines.insert(0, events)
                        break
                else:
                    lines.append(events)
    if window is None:
        raise ValueError(f"trace holds no {WINDOW!r} annotation")
    return Trace(ops, lines[0], window)


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


@contextmanager
def traced_window(trace_dir: str | None):
    """Trace the block (when ``trace_dir`` is given) and mark it as the
    window on the host."""
    import jax

    if trace_dir is None:
        yield
        return
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()
