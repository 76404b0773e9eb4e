"""Device busy milliseconds per SSSP search completed in the traced
window (profiler trace)."""


def read(run):
    if run.trace is None or run.trace.idle_share is None \
            or run.kind != "closed_sssp" or not run.searches:
        return None
    return run.trace.busy_s * 1e3 / run.searches
