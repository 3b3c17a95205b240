"""Work rate of the Pallas kernels: the program's `n_gemm_lanes` (lanes x
GEMM rows launched) of the window's queries, each query counted once as
`gemm_lanes_per_query` counts it, over the device microseconds of every
Mosaic custom call in the traced window. None without a trace or where the
program has no such counter."""


def read(run):
    if run.trace is None or run.trace.kernel_s <= 0:
        return None
    lanes = sum(max(getattr(r, "n_gemm_lanes", None) or 0 for _, r in res)
                for res in run.results if res)
    if not lanes:
        return None
    return lanes / (run.trace.kernel_s * 1e6)
