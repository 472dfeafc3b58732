"""Model step: model operations of the tokens actually computed in the
window (prompt tokens prefilled and output tokens; cached, pad and dead
lanes do not count), over the window and the chip's bf16 peak."""


def read(run):
    rounds = run.window_rounds()
    if not rounds or run.window.seconds <= 0:
        return None
    total = sum(run.round_work(r)["flops"] for r in rounds)
    if total <= 0:
        return None
    return 100.0 * total / run.window.seconds / run.peaks["bf16_flops"]
