"""Graph500 TEPS of a closed loop of searches, whatever kernel it drives:
the traversed input edges of every search completed in the window, over
the window's seconds (host clock).  A run that counted no traversed
edges has nothing to read."""


def read(run):
    if not run.searches or not run.traversed_edges:
        return None
    return run.traversed_edges / run.window_s
