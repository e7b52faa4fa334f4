"""Share of the window (%) in which MatchService's loop waited with nothing
queued (gnnpe_service_idle_seconds), so that the engine thread idled too.
Beside device_idle_share it splits the device's idle time into time the
service had no work and time the host was busy."""
NAME = "gnnpe_service_idle_seconds"


def read(run):
    w0, w1 = run.window
    if NAME not in run.counters1 or w1 <= w0:
        return None
    return 100.0 * run.hist_sum(NAME) / (w1 - w0)
