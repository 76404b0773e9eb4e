"""Host milliseconds per SSSP search in the copy of the finished
distances and parents to the host (program span ``sssp.fetch``)."""

from benchmark.spans import span_seconds


def read(run):
    if run.trace is None or run.kind != "closed_sssp" or not run.searches:
        return None
    seconds = span_seconds(run.trace, "sssp.fetch")
    return None if seconds is None else seconds * 1e3 / run.searches
