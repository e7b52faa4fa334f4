"""Mean wall milliseconds per applied update epoch in the window
(gnnpe_server_update_epoch_seconds)."""


def read(run):
    n = run.counter("gnnpe_server_update_epoch_seconds")
    return run.hist_sum("gnnpe_server_update_epoch_seconds") / n * 1e3 if n else None
