"""Pallas kernel executions in the traced window per window query."""


def read(run):
    if run.trace is None or run.trace.kernel_launches == 0:
        return None
    return run.trace.kernel_launches / run.n_queries
