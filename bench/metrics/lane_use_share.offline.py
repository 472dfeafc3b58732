"""Model step: lanes of the round programs that produced a token or
computed a prompt position, over the lanes they computed, in the window
(``engine.stats()`` deltas: ``lanes_used`` / ``lanes_dispatched``)."""


def read(run):
    a, b = run.window.stats_open, run.window.stats_close
    if "lanes_dispatched" not in b:
        return None
    done = b["lanes_dispatched"] - a["lanes_dispatched"]
    used = b["lanes_used"] - a["lanes_used"]
    return 100.0 * used / done if done > 0 else None
