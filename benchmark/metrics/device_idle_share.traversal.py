"""Percent of a traced window in which the device ran no operation
(profiler trace), whichever driver made the run."""


def read(run):
    if run.trace is None:
        return None
    return run.trace.idle_share
