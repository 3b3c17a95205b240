"""Stored slab bounds the service re-priced per window query: the
service's `slabs_repriced` counter over the window, per query."""


def read(run):
    if "slabs_repriced" not in run.counters:
        return None
    return run.counters["slabs_repriced"] / run.n_queries
