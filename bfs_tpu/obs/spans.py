"""Nestable wall-clock spans, exported as Chrome trace events and
written into the profiler's trace.

One span has two sinks:

- The bounded process-global buffer: each closed span becomes one Chrome
  ``"ph": "X"`` complete event (name, ts/dur in µs, pid/tid, args),
  which Perfetto and ``chrome://tracing`` load directly.  Each event also
  records its own ``id`` and its ``parent``: the id of the innermost span
  open on the same thread when it began (None at the top), so a reader
  gets the causal tree without inferring it from containment.  ``ts`` is
  wall-clock epoch µs (not a process monotonic zero): a bench killed and
  resumed journals each segment's events as they happened, and the
  stitched trace shows the gap between process generations instead of
  overlapping them.
- The profiler: when ``jax`` is already imported, the span also enters a
  ``jax.profiler.TraceAnnotation`` under its bare name (the attributes
  stay in the Chrome export).  With a ``jax.profiler`` trace running, the
  span lands on the thread's line of the trace, on the same clock as the
  device's operations, so a device gap can be laid against the program's
  own phases (``relay.fetch``, ``relay.relabel``, ...).  With no trace
  running the annotation costs about half a microsecond.

Spans are ON by default (``BFS_TPU_SPANS=0`` disables both sinks): one
``perf_counter_ns`` pair, a dict append and the annotation per span,
host-side only — nothing here ever touches a device value, which is what
keeps the API legal anywhere EXCEPT inside a declared hot region (the
analysis pass's OBS001 polices reads; span *writes* around a hot region
are the intended use: ``with span("repeat"): run()``).

Crash-durable traces: :func:`journal_spans` drains the buffer into a
``RunJournal`` record (``spans:<k>``, one per process generation) and
:func:`stitch_journal_trace` re-reads every generation's record from the
journal file into one trace — the SIGTERM path flushes still-open spans
first (:func:`flush_open_spans`) so an interrupted run leaves a usable
trace instead of a truncated one.

Everything in this module is stdlib-only (no jax, no numpy): the lint
stub path (tools/lint.py, tools/chaos_run.py) imports it for free, and
the profiler sink is used only once something else has imported jax.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time

from .. import knobs

#: Buffer bound: a serve process answering queries forever must not leak
#: memory through its own observability.  Past the cap new events are
#: dropped and counted (the drop count rides in every export).
MAX_EVENTS = 200_000

_lock = threading.Lock()
_events: list[dict] = []  # guarded-by: _lock
_dropped = 0  # guarded-by: _lock
_open: dict[int, dict] = {}  # guarded-by: _lock — span id -> start info
_next_id = [0]  # guarded-by: _lock
#: Per-thread stack of open span ids: the innermost is the parent of the
#: next span or marker the thread records.
_stack = threading.local()


def spans_enabled() -> bool:
    return knobs.get("BFS_TPU_SPANS")


def _wall_us() -> int:
    return time.time_ns() // 1_000


def _emit(event: dict) -> None:
    global _dropped
    with _lock:
        if len(_events) >= MAX_EVENTS:
            _dropped += 1
            return
        _events.append(event)


def _thread_stack() -> list:
    ids = getattr(_stack, "ids", None)
    if ids is None:
        ids = _stack.ids = []
    return ids


def _annotation(name: str):
    """An entered ``jax.profiler.TraceAnnotation`` named ``name``, or None
    while jax is not imported (this module never imports it first)."""
    if "jax" not in sys.modules:
        return None
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(name)
    ann.__enter__()
    return ann


class _Span:
    """One span: context manager AND decorator (``@span("name")``)."""

    __slots__ = ("name", "attrs", "_id", "_t0", "_ann")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self._id = None
        self._t0 = 0
        self._ann = None

    def __enter__(self):
        if not spans_enabled():
            return self
        ids = _thread_stack()
        self._t0 = time.perf_counter_ns()
        with _lock:
            _next_id[0] += 1
            self._id = _next_id[0]
            _open[self._id] = {
                "name": self.name,
                "ts": _wall_us(),
                "t0": self._t0,
                "tid": threading.get_ident(),
                "args": dict(self.attrs),
                "parent": ids[-1] if ids else None,
            }
        ids.append(self._id)
        self._ann = _annotation(self.name)
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._id is None:
            return False
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        dur_us = (time.perf_counter_ns() - self._t0) // 1_000
        ids = _thread_stack()
        if self._id in ids:
            ids.remove(self._id)
        with _lock:
            info = _open.pop(self._id, None)
        if info is not None:
            args = info["args"]
            if exc_type is not None:
                args = {**args, "error": exc_type.__name__}
            _emit({
                "name": self.name, "ph": "X", "ts": info["ts"],
                "dur": max(int(dur_us), 1), "pid": os.getpid(),
                "tid": info["tid"], "cat": "bfs_tpu", "args": args,
                "id": self._id, "parent": info["parent"],
            })
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with _Span(self.name, self.attrs):
                return fn(*a, **kw)

        return wrapper


def span(name: str, **attrs) -> _Span:
    """``with span("engine_init", scale=24): ...`` or ``@span("tick")``."""
    return _Span(name, attrs)


def instant(name: str, **attrs) -> None:
    """One zero-duration marker event (Chrome ``ph: "i"``) — eviction,
    cache invalidation, fault injection: things that happen, not last."""
    if not spans_enabled():
        return
    ids = _thread_stack()
    with _lock:
        _next_id[0] += 1
        own = _next_id[0]
    _emit({
        "name": name, "ph": "i", "ts": _wall_us(), "s": "p",
        "pid": os.getpid(), "tid": threading.get_ident(),
        "cat": "bfs_tpu", "args": dict(attrs),
        "id": own, "parent": ids[-1] if ids else None,
    })


def flush_open_spans(note: str = "flushed") -> int:
    """Close every still-open span NOW (SIGTERM/SIGALRM path): each gets
    its real duration so far plus ``args.flushed``, so an interrupted run's
    trace shows exactly which phase the signal landed in.  Returns the
    number of spans flushed.  Thread stacks are not unwound — the process
    is about to exit."""
    now_ns = time.perf_counter_ns()
    with _lock:
        open_now = list(_open.items())
        _open.clear()
    for sid, info in open_now:
        _emit({
            "name": info["name"], "ph": "X", "ts": info["ts"],
            "dur": max((now_ns - info["t0"]) // 1_000, 1),
            "pid": os.getpid(), "tid": info["tid"], "cat": "bfs_tpu",
            "args": {**info["args"], "flushed": note},
            "id": sid, "parent": info["parent"],
        })
    return len(open_now)


def snapshot_events() -> list[dict]:
    with _lock:
        return list(_events)


def drain_events() -> list[dict]:
    """Return and clear the buffer (the journal path: each process
    generation journals its own events exactly once)."""
    global _dropped
    with _lock:
        out = list(_events)
        _events.clear()
        _dropped = 0
        return out


def span_report() -> dict:
    """Per-name count + total seconds of CLOSED spans — the summary the
    metrics registry snapshot embeds."""
    out: dict[str, dict] = {}
    for ev in snapshot_events():
        if ev.get("ph") != "X":
            continue
        rec = out.setdefault(ev["name"], {"count": 0, "total_s": 0.0})
        rec["count"] += 1
        rec["total_s"] += ev.get("dur", 0) / 1e6
    return out


def chrome_trace(events: list[dict] | None = None) -> dict:
    """The Chrome/Perfetto trace document for ``events`` (default: the
    current buffer)."""
    evs = snapshot_events() if events is None else list(events)
    with _lock:
        dropped = _dropped
    doc = {"traceEvents": evs, "displayTimeUnit": "ms"}
    if dropped:
        doc["otherData"] = {"dropped_events": dropped}
    return doc


def export_chrome_trace(path: str, events: list[dict] | None = None) -> str:
    """Write the trace JSON atomically; returns ``path``."""
    doc = chrome_trace(events)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return path


# --------------------------------------------------------------- journal --

def journal_spans(jr) -> str | None:
    """Drain this process generation's span events into one durable
    ``spans:<k>`` record of ``jr`` (a RunJournal).  ``k`` counts prior
    generations, so a killed-and-resumed bench accumulates one record per
    segment and :func:`stitch_journal_trace` re-assembles them in order.
    No-op (returns None) when there is nothing to journal — with no
    journal the buffer is left intact for a later export, not drained."""
    if jr is None:
        return None
    events = drain_events()
    if not events:
        return None
    k = sum(1 for p in jr.phases() if p.startswith("spans:"))
    phase = f"spans:{k}"
    jr.put(phase, {"events": events})
    return phase


def stitch_journal_trace(journal_path: str) -> dict:
    """Chrome trace stitched from every ``spans:<k>`` record of a journal
    FILE (no config needed — the records are read leniently, crc-checked
    per line, torn tails skipped).  Wall-clock ``ts`` means the segments
    land on one coherent timeline with real gaps between generations."""
    from ..resilience.journal import read_records

    events: list[dict] = []
    spans_recs = []
    for rec in read_records(journal_path):
        if rec["phase"].startswith("spans:"):
            spans_recs.append(rec)
    spans_recs.sort(key=lambda r: int(r["phase"].split(":", 1)[1]))
    for rec in spans_recs:
        events.extend(rec["payload"].get("events", ()))
    return {"traceEvents": events, "displayTimeUnit": "ms"}
