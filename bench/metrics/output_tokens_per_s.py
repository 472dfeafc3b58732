"""Output tokens streamed to clients inside the window, per second of
the window (host clock at each token's arrival at its client)."""


def read(run):
    w = run.window
    return w.tokens_in_window / w.seconds if w.seconds > 0 else None
