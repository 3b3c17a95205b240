"""Queries answered per second: every window query over the whole window,
from the first query's send to the last one's answer. Host clock."""


def read(run):
    return run.n_queries / run.window_s
