"""Front-end and admission: median, over requests due in the
window, of the engine's grant stamp (``ServeRequest.grant_s``) minus
the time the request was due."""

import math

from benchlib.readings import pct


def read(run):
    return pct([((r.grant_s if math.isfinite(r.grant_s)
                  else r.due_s + run.missing_s(r)) - r.due_s) * 1e3
                for r in run.due_in_window()], 50)
