"""Sync primitives: median, over the traced rounds, of the host time in
the program's ``serve.plan`` span (the admission planner's dispatch and
the readback of its plan), from the trace."""

from benchlib.readings import pct


def read(run):
    steps = (run.trace or {}).get("steps")
    return pct([p * 1e3 for *_, p in steps], 50) if steps else None
