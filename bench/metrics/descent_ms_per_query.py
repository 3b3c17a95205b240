"""Host self time of the branch-and-bound slab descent ("search.descend":
prune masks and slab halving, bound pricing excluded) in the traced
window, per window query."""
from spans import self_ms_per_query


def read(run):
    return self_ms_per_query(run, "dxpta.search.descend")
