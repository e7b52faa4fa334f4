"""95th-percentile match latency (ms): due time to response, every match due
in the window; a request not served counts as slower than every served one."""
from gnnbench.cell import percentile


def read(run):
    v = percentile(run.match_latencies_s(), 95)
    return None if v is None else v * 1e3
