"""Host self time of the search driver's "search" spans (argument
resolution, best-first ordering, batch slicing, incumbent merges) in the
traced window, per window query."""
from spans import self_ms_per_query


def read(run):
    return self_ms_per_query(run, "dxpta.search")
