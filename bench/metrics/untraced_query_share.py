"""Share of the window's query time under no program span: the self time
of the harness's "bench.query" spans over their summed duration. The
program's spans cover the rest, so the per-layer span times add up to the
query time."""
from spans import PROGRAM_PREFIX, QUERY


def read(run):
    self_s = getattr(run.trace, "span_self_s", None)
    if not self_s or not any(k.startswith(PROGRAM_PREFIX) for k in self_s):
        return None
    if run.trace.query_s <= 0:
        return None
    return self_s.get(QUERY, 0.0) / run.trace.query_s
