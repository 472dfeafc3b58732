"""Front-end: median time from the end of one traced ``serve.step`` span
to the start of the next (the front-end's work between rounds), from
the trace."""

from benchlib.readings import pct


def read(run):
    gaps = (run.trace or {}).get("step_gaps_s")
    return pct([g * 1e3 for g in gaps], 50) if gaps else None
