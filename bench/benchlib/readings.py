"""What one run leaves for the metric readers, and shared arithmetic.

A reader (``bench/metrics/<name>.py``) gets a :class:`Run` and returns
a number, or None where it finds nothing to read (then the metric is
left out of the result line; a share of a roofline or of a peak is
never reported as 0 for want of data).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import numpy as np

from . import flops


@dataclasses.dataclass
class Run:
    config: Dict[str, Any]
    window: Any                       # drive.Window
    setup_s: float
    peaks: Dict[str, float]
    trace: Optional[Dict[str, Any]] = None   # trace.reduce output

    # ---------------------------------------------------------- requests
    def due_in_window(self) -> List[Any]:
        return [r for r in self.window.reqs if r.in_window]

    def missing_s(self, r) -> float:
        """What a request that never answered counts as in a tail: the
        longest it could have waited in this run."""
        from .drive import TAIL_WAIT_S
        return self.window.t_close + TAIL_WAIT_S - r.due_s

    # ------------------------------------------------------------ rounds
    def rounds_between(self, t0: float, t1: float):
        return [r for r in self.window.rounds if t0 < r.t <= t1]

    def window_rounds(self):
        return self.rounds_between(self.window.t_open, self.window.t_close)

    def traced_rounds(self):
        w = self.window
        if not math.isfinite(w.trace_t0):
            return []
        return self.rounds_between(w.trace_t0, w.trace_t1)

    def round_work(self, rd) -> Dict[str, float]:
        """Useful operations and least bytes of one round: decode tokens
        at the decoding rows' mean context, prompt tokens of the chunk
        (without the output head), first tokens' output head; weights
        read once per sequential decode step, live K/V read once per
        decode token, K/V written once per computed token."""
        c = self.config
        m = flops.dims(c)
        decode = rd.out_tokens - rd.first_tokens
        ctx = rd.ctx_decoding / rd.n_decoding if rd.n_decoding else 0.0
        f = (decode * flops.token_flops(c, ctx, logits=True)
             + rd.prefill_tokens * flops.token_flops(c, 0, logits=False)
             + rd.first_tokens * 2.0 * m["head_params"])
        steps = (min(2, math.ceil(decode / rd.n_decoding))
                 if rd.n_decoding and decode > 0 else
                 (1 if rd.prefill_tokens else 0))
        b = (steps * flops.weight_bytes(c)
             + decode * ctx * m["kv_bytes_per_token"]
             + (decode + rd.prefill_tokens) * m["kv_bytes_per_token"])
        return {"flops": f, "bytes": b}


def pct(values, q: float) -> Optional[float]:
    vals = [v for v in values if v is not None]
    if not vals:
        return None
    return float(np.percentile(np.asarray(vals, np.float64), q))


def round_interval_ms(run: Run) -> Optional[float]:
    """Median time between consecutive round hooks in the window."""
    ts = [r.t for r in run.window_rounds()]
    if len(ts) < 2:
        return None
    return float(np.median(np.diff(ts))) * 1e3


def idle_share(run: Run) -> Optional[float]:
    t = run.trace
    if not t or t.get("window_s", 0) <= 0 or t.get("busy_s", 0) <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
