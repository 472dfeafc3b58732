"""Scheduler round: median, over the traced rounds, of the host time in
the program's ``serve.step`` span less its ``serve.wait`` (the host
blocked on the device's results), from the trace."""

from benchlib.readings import pct


def read(run):
    steps = (run.trace or {}).get("steps")
    return pct([(d - w) * 1e3 for d, w, _ in steps], 50) if steps else None
