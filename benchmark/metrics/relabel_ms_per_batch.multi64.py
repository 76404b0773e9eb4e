"""Host milliseconds per batch of sources in the engine's relabel of the
fetched trees to the original ids (program span ``relay.relabel``)."""

from benchmark.spans import span_seconds


def read(run):
    if run.trace is None or run.kind != "closed_search" \
            or run.facts.get("batch", 1) == 1 or not run.requests:
        return None
    seconds = span_seconds(run.trace, "relay.relabel")
    return None if seconds is None else seconds * 1e3 / run.requests
