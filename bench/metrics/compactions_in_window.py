"""Background compactions installed while the window ran
(MatchService.counters["compactions_installed"])."""


def read(run):
    c = run.service_counters
    return c["compactions1"] - c["compactions0"]
