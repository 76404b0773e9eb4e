"""The least HBM bytes a Graph500 kernel 3 search must move, whatever
the implementation: each directed edge of the key's component read once
(source, target and weight, 4 B each) and each vertex's distance and
parent written once (4 B each).  A search that sweeps only its live
frontier, or a hand-written kernel, is measured against the same bytes,
so the share of the roofline cannot pass 100%."""

EDGE_BYTES = 12
VERTEX_BYTES = 8


def window_bytes(run) -> int:
    """The least bytes of the searches a closed window completed.  Every
    input edge is stored both ways, so a key's component holds twice its
    Graph500 traversed edges (``runs.traversed_edges``) as directed
    edges."""
    return (EDGE_BYTES * 2 * run.traversed_edges
            + VERTEX_BYTES * run.facts["num_vertices"] * run.searches)
