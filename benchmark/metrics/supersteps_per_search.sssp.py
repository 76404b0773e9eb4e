"""Min-plus supersteps per SSSP search in the window: the program's
counter ``sssp_supersteps`` over ``sssp_searches``, both taken by the
driver around the window."""


def read(run):
    searches = run.facts.get("sssp_searches")
    if run.kind != "closed_sssp" or not searches:
        return None
    return run.facts["sssp_supersteps"] / searches
