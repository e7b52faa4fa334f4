"""Milliseconds per query of crossing between MatchService's event loop and
its engine thread, both ways (gnnpe_service_handoff_seconds, legs to_engine
and to_loop)."""
NAME = "gnnpe_service_handoff_seconds"


def read(run):
    n = run.queries_in_window()
    if NAME not in run.counters1 or not n:
        return None
    s = run.hist_sum(NAME, leg="to_engine") + run.hist_sum(NAME, leg="to_loop")
    return s / n * 1e3
