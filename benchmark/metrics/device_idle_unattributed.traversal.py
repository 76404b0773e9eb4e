"""Percent of the device's idle time in a closed loop's traced window
during which no ``relay.*`` span of the engine is open: idle time the
program's spans do not account for (profiler trace and program spans)."""

from benchmark.spans import idle_seconds


def read(run):
    if run.trace is None or run.kind != "closed_search":
        return None
    split = idle_seconds(run.trace)
    if split is None or split[0] <= 0:
        return None
    return 100.0 * split[1] / split[0]
