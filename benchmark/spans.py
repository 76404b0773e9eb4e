"""The program's own spans and the superstep kinds, read from a traced
window (``trace.Trace``).

The engine marks each boundary of a search with a span that is also a
profiler annotation on the driving thread (``bfs_tpu/obs/spans.py``):
``relay.search`` (``relay.batch`` for a batch) around ``relay.launch``,
``relay.device_wait``, ``relay.fetch``, ``relay.rerun_unpacked`` and
``relay.relabel``.  Its Pallas kernels carry stable names, the Beneš
passes ``relay_net_*``.  What is read here uses only a trace's public
``host``, ``ops`` and ``window``:

- the seconds of the host events of one name, clipped to the window;
- the device's idle seconds in the window, and the part of them during
  which no ``relay.*`` span is open;
- the device time of each outermost ``conditional`` instance, the
  per-superstep dispatch of the relay program: dense when it contains a
  ``relay_net_*`` kernel, sparse otherwise.

Each returns None when the trace holds nothing of what it reads, as a
program without these spans or names gives.
"""

from __future__ import annotations

import re

import numpy as np

SPAN_PREFIX = "relay."
#: A Beneš pass: its instruction name holds the kernel's name, with a
#: prefix where a transformation wraps it (``vmap_relay_net_local_.15``).
_NET_KERNEL = re.compile(r"^%?[\w.-]*relay_net_[\w.-]* = ")
_CONDITIONAL = re.compile(r"^%?[\w.-]+ = .+? conditional\(")


def _union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(trace, start, end):
    lo, hi = trace.window
    return np.clip(start, lo, hi), np.clip(end, lo, hi)


def _devices(trace) -> list[str]:
    return [dev for dev, (names, _, _) in sorted(trace.ops.items()) if names]


def span_seconds(trace, name: str) -> float | None:
    """Seconds of the host events named ``name`` inside the window; None
    when the trace holds no such event."""
    lo, hi = trace.window
    spans = [(max(s, lo), min(e, hi)) for n, s, e in trace.host if n == name]
    if not spans:
        return None
    return sum(e - s for s, e in spans if e > s) * 1e-9


def idle_seconds(trace) -> tuple[float, float] | None:
    """``(idle, unattributed)``: the first active device's idle seconds in
    the window, and the part of them during which no ``relay.*`` span is
    open on the window's thread.  None without a device operation or a
    program span."""
    devs = _devices(trace)
    spans = [(s, e) for n, s, e in trace.host if n.startswith(SPAN_PREFIX)]
    if not devs or not spans:
        return None
    lo, hi = trace.window
    _, start, end = trace.ops[devs[0]]
    start, end = _clip(trace, start, end)
    gaps, cursor = [], lo
    for s, e in _union(zip(start.tolist(), end.tolist())):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    covered = _union((max(s, lo), min(e, hi)) for s, e in spans)
    idle = sum(e - s for s, e in gaps)
    inside, j = 0.0, 0
    for s, e in gaps:
        while j < len(covered) and covered[j][1] <= s:
            j += 1
        k = j
        while k < len(covered) and covered[k][0] < e:
            inside += min(e, covered[k][1]) - max(s, covered[k][0])
            k += 1
    return idle * 1e-9, (idle - inside) * 1e-9


def superstep_seconds(trace) -> tuple[float, float] | None:
    """``(dense, sparse)`` device seconds of the window's outermost
    ``conditional`` instances, averaged over the active devices: dense
    when the instance contains a ``relay_net_*`` kernel.  None when the
    window holds no such kernel or no conditional."""
    devs = _devices(trace)
    dense = sparse = 0.0
    seen_kernel = seen_cond = False
    for dev in devs:
        names, start, end = trace.ops[dev]
        start, end = _clip(trace, start, end)
        kernels = np.sort(np.array(
            [s for n, s, e in zip(names, start.tolist(), end.tolist())
             if e > s and _NET_KERNEL.match(n)], np.float64))
        conds = sorted(
            (s, -e) for n, s, e in zip(names, start.tolist(), end.tolist())
            if e > s and _CONDITIONAL.match(n))
        seen_kernel |= kernels.size > 0
        reach = -np.inf
        for s, neg_e in conds:
            e = -neg_e
            if e <= reach:
                continue  # inside an outer conditional
            reach = e
            seen_cond = True
            i = np.searchsorted(kernels, s)
            if i < kernels.size and kernels[i] < e:
                dense += e - s
            else:
                sparse += e - s
    if not (seen_kernel and seen_cond):
        return None
    return dense * 1e-9 / len(devs), sparse * 1e-9 / len(devs)
