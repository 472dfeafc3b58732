"""Sync primitives: device time per round of the admission planner's
Pallas kernel (``sleeping_semaphore_kernel``), from the trace."""


def read(run):
    t = run.trace
    n = len(run.traced_rounds())
    if not t or not n or t.get("planner_device_s", 0) <= 0:
        return None
    return t["planner_device_s"] / n * 1e6
