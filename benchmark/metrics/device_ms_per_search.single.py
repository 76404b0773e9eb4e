"""Device busy milliseconds per single-source search completed in the
traced window (profiler trace)."""


def read(run):
    if run.trace is None or run.trace.idle_share is None \
            or run.kind != "closed_search" \
            or run.facts.get("batch") != 1 or not run.searches:
        return None
    return run.trace.busy_s * 1e3 / run.searches
