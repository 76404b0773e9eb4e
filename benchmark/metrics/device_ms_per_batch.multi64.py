"""Device busy milliseconds per batch of sources completed in the traced
window (profiler trace)."""


def read(run):
    if run.trace is None or run.trace.idle_share is None \
            or run.kind != "closed_search" \
            or run.facts.get("batch", 1) == 1 or not run.requests:
        return None
    return run.trace.busy_s * 1e3 / run.requests
