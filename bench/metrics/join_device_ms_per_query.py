"""Device milliseconds per query of the ops that ran on the chip while the
engine's `join` stage span was open (profiler trace, with the program's
stage spans put on its clock).  Beside join_ms_per_query it shows how much
of that host clock is device work and how much is host work or waiting."""
from gnnbench import tracefile


def read(run):
    ev = run.trace_events
    n = run.queries_in_window()
    if not ev or not n:
        return None
    spans = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ev if e["name"] == "stage.join"]
    if not spans:
        return None
    return tracefile.device_time_in(ev, spans) / n * 1e3
