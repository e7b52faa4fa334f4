"""95th-percentile update visibility (ms): from an update's due time until the
inner server's applied-update count (FIFO) covers it."""
import math

from gnnbench.cell import percentile


def read(run):
    v = percentile(run.update_visible_s(), 95)
    return None if v is None or math.isinf(v) else v * 1e3
