"""Median match latency (ms): due time to response, every match due in the window."""
from gnnbench.cell import percentile


def read(run):
    v = percentile(run.match_latencies_s(), 50)
    return None if v is None else v * 1e3
