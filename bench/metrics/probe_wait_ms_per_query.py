"""Host milliseconds per query blocked on the stacked probe's device results
(gnnpe_engine_step_seconds{stage=probe,step=wait}: cell counts, pair totals,
per-probe row counts); the rest of probe_ms_per_query is host work and
dispatch."""


def read(run):
    n = run.queries_in_window()
    if "gnnpe_engine_step_seconds" not in run.counters1 or not n:
        return None
    return run.hist_sum("gnnpe_engine_step_seconds", stage="probe", step="wait") / n * 1e3
