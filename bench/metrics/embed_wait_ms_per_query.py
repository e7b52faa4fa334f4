"""Host milliseconds per query spent waiting on the device for the star
encoder's outputs (gnnpe_engine_step_seconds{stage=embed,step=wait}); the
rest of embed_ms_per_query is building star tensors and dispatching."""


def read(run):
    n = run.queries_in_window()
    if "gnnpe_engine_step_seconds" not in run.counters1 or not n:
        return None
    return run.hist_sum("gnnpe_engine_step_seconds", stage="embed", step="wait") / n * 1e3
