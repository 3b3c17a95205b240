"""95th percentile of the caller-side latency of every window query,
failed ones included. Host clock."""
from stats import percentile


def read(run):
    return percentile(run.latencies_s, 95) * 1e3
