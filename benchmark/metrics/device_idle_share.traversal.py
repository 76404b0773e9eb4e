"""Percent of a closed loop's traced window in which the device ran no
operation (profiler trace)."""


def read(run):
    if run.trace is None or run.kind != "closed_search":
        return None
    return run.trace.idle_share
