"""Host self time of the float64 reference work ("search.refine": the
incumbent's and the result's `eval_full`, running-front merges, the final
frontier) in the traced window, per window query."""
from spans import self_ms_per_query


def read(run):
    return self_ms_per_query(run, "dxpta.search.refine")
