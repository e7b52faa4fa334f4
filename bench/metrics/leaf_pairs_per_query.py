"""Leaf (query path, index row) pairs the probe scanned per query in the
window (gnnpe_funnel_total{stage=leaf_pairs})."""


def read(run):
    n = run.queries_in_window()
    return run.counter("gnnpe_funnel_total", stage="leaf_pairs") / n if n else None
