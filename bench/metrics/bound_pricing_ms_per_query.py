"""Host self time of slab lower-bound pricing ("search.bounds",
`SlabBoundEvaluator.lower_bounds_batch`) in the traced window, per window
query."""
from spans import self_ms_per_query


def read(run):
    return self_ms_per_query(run, "dxpta.search.bounds")
