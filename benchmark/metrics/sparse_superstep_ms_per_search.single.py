"""Device milliseconds per single-source search in sparse supersteps:
the relay program's per-superstep conditionals that hold no Beneš
``relay_net_*`` kernel (profiler trace)."""

from benchmark.spans import superstep_seconds


def read(run):
    if run.trace is None or run.kind != "closed_search" \
            or run.facts.get("batch") != 1 or not run.searches:
        return None
    split = superstep_seconds(run.trace)
    return None if split is None else split[1] * 1e3 / run.searches
