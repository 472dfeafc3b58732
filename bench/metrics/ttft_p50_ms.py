"""Median, over every request due in the window, of its first
token's arrival at the client minus the time it was due. A request
that never answered counts as the longest wait the run allows."""

from benchlib.readings import pct


def read(run):
    reqs = run.due_in_window()
    return pct([(r.first_s - r.due_s if r.n_out else run.missing_s(r)) * 1e3
                for r in reqs], 50)
