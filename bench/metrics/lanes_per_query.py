"""Lanes the kernel launches ran, padding and masked lanes included: the
summed `lanes` stat of the traced window's "launch" spans, per window
query. Against `evals_per_query`, the kernel wrappers' useful share."""


def read(run):
    lanes = getattr(run.trace, "lanes", None)
    if not lanes:
        return None
    return lanes / run.n_queries
