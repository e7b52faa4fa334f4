"""Host milliseconds per query in the engine's `plan` stage over the window
(gnnpe_engine_stage_seconds, a host clock: see PERF.md on what it covers)."""


def read(run):
    n = run.queries_in_window()
    s = run.hist_sum("gnnpe_engine_stage_seconds", stage="plan")
    return s / n * 1e3 if n and s > 0 else None
