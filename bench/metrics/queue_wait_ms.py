"""Mean milliseconds a request waited in MatchService's queue, from being
queued to the start of its tick (gnnpe_service_queue_wait_seconds)."""
NAME = "gnnpe_service_queue_wait_seconds"


def read(run):
    n = run.counter(NAME)
    if NAME not in run.counters1 or not n:
        return None
    return run.hist_sum(NAME) / n * 1e3
