"""Host milliseconds per query blocked on the device join's results
(gnnpe_engine_step_seconds{stage=join,step=wait}: row counts per join step,
the compaction's counts, the refine's rows); the rest of join_ms_per_query
is host work and dispatch."""


def read(run):
    n = run.queries_in_window()
    if "gnnpe_engine_step_seconds" not in run.counters1 or not n:
        return None
    return run.hist_sum("gnnpe_engine_step_seconds", stage="join", step="wait") / n * 1e3
