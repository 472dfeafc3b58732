"""Attention read path: device self time of the ops in the program's
``paged_attention`` name scope, over the device time of the round
programs, in the trace."""


def read(run):
    t = run.trace or {}
    scoped, rounds = t.get("scoped_device_s", 0), t.get("round_device_s", 0)
    return 100.0 * scoped / rounds if scoped > 0 and rounds > 0 else None
