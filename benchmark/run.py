"""Run one cell of the benchmark once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its graph maker, its traffic mix, its
driver and its metrics are found by name from ``BENCHMARK.json`` (see
``harness.py``).  The run makes its
inputs from ``--seed``, builds the program through its public entry
points with the shipped defaults, warms every shape its traffic uses,
measures for ``--seconds`` and checks a seeded sample of the answers
against the plain reference.  Earlier lines on standard error say what the
run did: the engine's chosen arms, the layout's cache, the set-up's
stages and compiles, compiles in the window, and last the compared
numbers beside their limits.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402
from benchmark.runs import Context  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def execute(args, *, t_start: float = T_START, root: str = harness.ROOT,
            devices=None, meter=None, **swap) -> dict:
    """One run of one cell; returns the result object.  ``devices``,
    ``meter`` and ``swap`` (keyword hooks of the cell's driver, such as
    ``make_engine``) let tests drive a run without the chip, without
    touching JAX's global compile cache and with the timed path broken."""
    bench = harness.load_benchmark(root)
    cell, config, params = harness.load_cell(bench, args.workload, root)
    driver = harness.load_plugin("drivers", params["driver"], root)
    make_edges = harness.load_plugin(
        "graphs", config["graph"]["generator"], root).edges
    if devices is None:
        devices = harness.require_chips(cell["chips"])
    caches = {}
    if meter is None:
        from bfs_tpu.config import enable_compile_cache

        # JAX's persistent cache at a fixed path inside the checkout
        # (or where JAX_COMPILATION_CACHE_DIR says): every program is
        # written, so only a cell's first run in a checkout compiles.
        caches = enable_compile_cache(min_compile_seconds=0.0)
        meter = harness.CompileMeter()
    harness.note("run", {"workload": args.workload, "seed": args.seed,
                         "seconds": args.seconds, "trace": args.trace,
                         "device_kind": devices[0].device_kind,
                         "devices": len(devices), **caches})
    trace_dir = (os.path.join(root, ".bench_cache", "trace", args.workload)
                 if args.trace else None)
    ctx = Context(config, params, args.seed, args.seconds, trace_dir, meter,
                  t_start, devices, make_edges)
    run = driver.drive(ctx, **swap)
    run.peaks = harness.load_peaks(devices[0].device_kind, root) \
        if devices[0].platform == "tpu" else {}
    if trace_dir is not None:
        from benchmark import trace as tr

        run.trace = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    harness.note("setup", {"setup_s": run.setup_s,
                           "stages_s": run.facts.get("setup_stages"),
                           "compile": run.facts.get("setup_compile")})
    metrics = harness.read_metrics(
        harness.cell_metrics(bench, args.workload, bool(args.trace)), run,
        root)
    device = dict(run.device)
    result = {
        "correct": harness.checks_passed(run.checks),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = run.checks
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = execute(args)
    except harness.BenchError as exc:
        print(f"benchmark: {exc}", file=sys.stderr, flush=True)
        return 2
    harness.print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Leave without interpreter teardown, so that the compared numbers
    # stay the last lines on standard error.
    os._exit(code)
