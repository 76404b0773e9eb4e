"""What every run shares: finding a cell's pieces by name, the compile
meter, the device check and the result line.

Every piece of a cell is found by the name that ``BENCHMARK.json`` or a
file it names gives it, so a later change adds a cell, a configuration,
a traffic mix, a driver, a graph maker or a metric as new files and new
entries, and edits no file that is there:

- a configuration is the JSON file that its entry's ``file`` names; its
  ``graph.generator`` names its graph maker,
  ``benchmark/graphs/<generator>.py``, whose ``edges(graph)`` returns the
  edge list;
- a traffic mix is ``benchmark/traffic/<traffic>.json``, parameters
  whose ``driver`` names the loop, ``benchmark/drivers/<driver>.py``,
  whose ``drive(ctx, **swap)`` runs the cell (``runs.py``);
- a metric, end to end or per layer, is ``benchmark/metrics/<name>.py``,
  whose ``read(run)`` returns the number or ``None`` when the run holds
  nothing to read.

A new kernel's driver (an SSSP, another engine) writes only what is its
own: its engine, the ``ask(sources)`` that calls it, its check against
the reference and its ``control()``.  The closed-loop window
(``runs.closed_window``) and the Graph500 edge count
(``runs.traversed_edges``) are shared, so ``teps`` means the same in
every cell, and a reader that does not depend on the program (``teps``,
``device_idle_share.traversal``) reads any driver's run: a cell joins
it by its entry's ``workloads`` list alone.
"""

from __future__ import annotations

import importlib.util
import json
import logging
import math
import os
import sys

#: The checkout's root: ``BENCHMARK.json`` and the program live here.
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, unknown name)."""


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _named(items: list, name: str, what: str) -> dict:
    for item in items:
        if item["name"] == name:
            return item
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(bench: dict, workload: str,
              root: str = ROOT) -> tuple[dict, dict, dict]:
    """``(cell, configuration, traffic)`` of one workload, by name."""
    cell = _named(bench["workloads"], workload, "workload")
    entry = _named(bench["configs"], cell["config"], "configuration")
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return cell, config, traffic


def cell_metrics(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metric entries a run of ``workload`` reports: the end-to-end
    ones without a trace, the per-layer ones with it.  An entry with a
    ``workloads`` list belongs only to those cells."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries
            if "workloads" not in m or workload in m["workloads"]]


def load_plugin(folder: str, name: str, root: str = ROOT):
    """The module ``benchmark/<folder>/<name>.py``, loaded from its file."""
    path = os.path.join(root, "benchmark", folder, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no {folder} file for {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(name: str, root: str = ROOT):
    """The ``read(run)`` function of ``benchmark/metrics/<name>.py``."""
    return load_plugin("metrics", name, root).read


def read_metrics(entries: list[dict], run, root: str = ROOT) -> dict:
    """``{name: {"value", "unit"}}`` for every metric whose reader finds
    something to read in ``run``."""
    out = {}
    for m in entries:
        value = load_reader(m["name"], root)(run)
        if value is None:
            continue
        value = float(value)
        if not math.isfinite(value):
            raise BenchError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def load_peaks(device_kind: str, root: str = ROOT) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(os.path.join(root, "benchmark", "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise BenchError(f"device kind {device_kind!r} is not in peaks.json")
    return table["devices"][device_kind]


def require_chips(chips: int):
    """The TPU devices a cell asks for, or :class:`BenchError`: a run
    measures the chip and never falls back to the CPU."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise BenchError(f"no TPU found (JAX platform {platform!r})")
    if len(devices) < chips:
        raise BenchError(f"cell asks for {chips} chips, JAX sees {len(devices)}")
    return devices


class CompileMeter:
    """Backend compile seconds, compiles, persistent-cache hits and
    misses, from JAX's own monitoring events, and the names of programs
    that missed the persistent cache, from JAX's compiler log."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.missed: list[str] = []
        meter = self

        class MissLog(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
                    meter.missed.append(msg.split("'")[1] if "'" in msg else msg)
                elif record.levelno >= logging.WARNING:
                    print(self.format(record), file=sys.stderr)

        log = logging.getLogger("jax._src.compiler")
        log.addHandler(MissLog(logging.DEBUG))
        log.setLevel(logging.DEBUG)
        log.propagate = False

        def on_duration(event, duration, **_):
            if event.endswith("backend_compile_duration"):
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "missed": list(self.missed)}

    @staticmethod
    def since(before: dict, after: dict) -> dict:
        out = {k: after[k] - before[k] for k in
               ("compile_s", "compiles", "cache_hits", "cache_misses")}
        out["missed"] = after["missed"][len(before["missed"]):]
        return out


def note(tag: str, doc: dict) -> None:
    """One earlier line of the run, on standard error."""
    print(f"[bench] {tag} {json.dumps(doc, default=str)}", file=sys.stderr,
          flush=True)


def device_info(devices) -> dict:
    """The result's ``device``: as JAX reports it, with the peak memory
    of the fullest chip."""
    dev = devices[0]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def checks_passed(checks: dict) -> bool:
    """Every compared number at or under its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def print_checks(checks: dict) -> None:
    """The compared numbers beside their limits: the run's last lines on
    standard error."""
    for name, c in checks.items():
        print(f"[bench] check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
