"""KV page manager: prompt tokens served from a shared or cached prefix
over prompt tokens admitted, in the window (``engine.stats()`` deltas:
``prefill_tokens_saved`` / (``prefill_tokens`` + ``prefill_tokens_saved``))."""


def read(run):
    a, b = run.window.stats_open, run.window.stats_close
    saved = b["prefill_tokens_saved"] - a["prefill_tokens_saved"]
    done = b["prefill_tokens"] - a["prefill_tokens"]
    return 100.0 * saved / (saved + done) if saved + done > 0 else None
