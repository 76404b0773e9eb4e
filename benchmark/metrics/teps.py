"""Graph500 TEPS of a closed loop: the traversed input edges of every
search completed in the window, over the window's seconds (host clock)."""


def read(run):
    if run.kind != "closed_search" or not run.searches:
        return None
    return run.traversed_edges / run.window_s
