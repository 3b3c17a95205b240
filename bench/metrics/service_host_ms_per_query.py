"""Host self time of the service's spans ("service.query": memo and base
lookup and dispatch; "service.reprice": the warm delta's ledger and
point-store re-pricing) in the traced window, per window query."""
from spans import self_ms_per_query


def read(run):
    return self_ms_per_query(run, "dxpta.service.query",
                             "dxpta.service.reprice")
