"""Median, over the requests due in the window, of (last token
- first token) / (output tokens - 1), as the client saw them. A request
that did not finish counts as the longest wait the run allows."""

from benchlib.readings import pct


def read(run):
    vals = []
    for r in run.due_in_window():
        if r.state == "finished" and r.n_out >= 2:
            vals.append((r.last_s - r.first_s) / (r.n_out - 1) * 1e3)
        elif r.state != "finished":
            vals.append(run.missing_s(r) * 1e3)
    return pct(vals, 50)
