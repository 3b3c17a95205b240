"""Set-up time: process start to the first timed query (JAX and TPU start,
compile-cache loads, the warm-up of the cell's launch shapes and, in warm
cells, the base query). Host clock."""


def read(run):
    return run.setup_s
