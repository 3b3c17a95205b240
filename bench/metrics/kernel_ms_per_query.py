"""Device time of every Pallas (Mosaic custom-call) kernel in the traced
window, per window query. Per-kernel names go to the breakdown."""


def read(run):
    if run.trace is None or run.trace.kernel_launches == 0:
        return None
    return run.trace.kernel_s * 1e3 / run.n_queries
