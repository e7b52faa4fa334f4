"""Times per query the engine thread waited for device results on the served
path, all stages (gnnpe_engine_device_syncs_total; one read of several
arrays counts once)."""
NAME = "gnnpe_engine_device_syncs_total"


def _total(snapshot: dict) -> float:
    m = snapshot.get(NAME)
    return sum(v["value"] for v in m["values"]) if m else 0.0


def read(run):
    n = run.queries_in_window()
    if NAME not in run.counters1 or not n:
        return None
    return (_total(run.counters1) - _total(run.counters0)) / n
