"""Mean host milliseconds inside MatchService.submit (admission and
plan_cost on the event loop), on the benchmark's clock."""
import statistics


def read(run):
    return statistics.fmean(run.admit_s) * 1e3 if run.admit_s else None
