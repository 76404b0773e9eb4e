"""Share of the HBM roofline reached by the SSSP searches of the traced
window: the least bytes those searches must move (``roofline_sssp.py``)
at the chip's peak bandwidth, over the device's busy time in the window,
which the searches' programs fill."""

from benchmark.roofline_sssp import window_bytes


def read(run):
    if run.trace is None or run.trace.idle_share is None \
            or run.kind != "closed_sssp" or not run.peaks \
            or not run.searches or run.trace.busy_s <= 0:
        return None
    return (100.0 * window_bytes(run) / run.peaks["hbm_bytes_per_s"]
            / run.trace.busy_s)
