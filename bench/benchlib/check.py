"""Whether what the timed window served is correct.

After the window has closed and the program's state is freed, the
window's requests go through the plain reference
(``bench/reference/<name>.py``) at its own float32: the longest request
(the most prompt and served tokens) on every token it served, and every
other request that streamed tokens on the first ``SAMPLE_TOKENS //
capacity`` it streamed inside the window. So every row that was live in
the window is compared, whichever slot it held, and a fault in part of
the batch cannot hide in rows the sample left out. Each served token is
teacher-forced at its position (prompt plus the tokens served before
it), and the number compared is the widest gap by which a served
token's reference logit lies below the reference's best at that
position, over that position's largest |logit|.

The limits and the readings behind them are in PERF.md ("How correct is
decided"); each run prints every number beside its limit.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List

import numpy as np

SAMPLE_TOKENS = 800    # served tokens compared over the rows of a full batch
ROW_BLOCK = 256        # reference rows are padded to a multiple of this


@dataclasses.dataclass
class Slice:
    req: Any           # drive.Req
    lo: int            # first served token compared
    hi: int            # one past the last


def is_cached(req, chunk: int) -> bool:
    """The prompt was served (in part) from shared or cached pages: it
    took fewer prefill chunks than its length needs."""
    return req.prefill_chunks < math.ceil(req.prompt.size / chunk)


def sample(reqs, capacity: int) -> List[Slice]:
    """Every request of the window that streamed tokens (finished ones,
    and offline, where the window closes on rows still decoding, the
    tokens a request had streamed by then): the longest whole, each
    other on its first tokens inside the window."""
    done = [r for r in reqs if r.in_window and r.n_out > 0
            and r.state != "failed"]
    if not done:
        return []
    per = max(SAMPLE_TOKENS // capacity, 1)
    longest = max(done, key=lambda r: (r.prompt.size + r.n_out, -r.due_s))
    out = [Slice(longest, 0, longest.n_out)]
    for r in done:
        if r is not longest:
            lo = min(r.n_before, max(r.n_out - per, 0))
            out.append(Slice(r, lo, min(lo + per, r.n_out)))
    return out


def gap_fn(jax, ref, c, control: bool):
    """One jitted reference reading per padded shape."""

    @jax.jit
    def run(w, tokens, rows, served):
        return ref.gaps(c, w, tokens, rows, served, fp8_rows=control)
    return run


def compare(jax, ref, c, w, picked: List[Slice], control: bool = False
            ) -> Dict[str, Any]:
    """Widest served-token gap over the sample (and the fp8 control's
    widest gap at the same positions, when asked)."""
    import jax.numpy as jnp
    import time
    fn = gap_fn(jax, ref, c, control)
    worst, worst_ctl, n_tok = 0.0, 0.0, 0
    seen, compile_s = set(), 0.0
    for sl in picked:
        r = sl.req
        served = np.asarray(r.tokens[:sl.hi], np.int32)
        seq = np.concatenate([r.prompt, served[:-1]]).astype(np.int32)
        L = ref.padded_len(seq.size)
        tokens = np.zeros(L, np.int32)
        tokens[:seq.size] = seq
        n = sl.hi - sl.lo
        R = -(-n // ROW_BLOCK) * ROW_BLOCK
        rows = np.zeros(R, np.int32)
        rows[:n] = r.prompt.size - 1 + np.arange(sl.lo, sl.hi)
        srv = np.zeros(R, np.int32)
        srv[:n] = served[sl.lo:]
        t = time.perf_counter()
        out = fn(w, jnp.asarray(tokens), jnp.asarray(rows), jnp.asarray(srv))
        worst = max(worst, float(np.max(np.asarray(out["served"])[:n])))
        if (L, R) not in seen:          # a new shape: compiled (or loaded)
            seen.add((L, R))
            compile_s += time.perf_counter() - t
        if control:
            worst_ctl = max(worst_ctl,
                            float(np.max(np.asarray(out["control"])[:n])))
        n_tok += n
    res = {"served_gap": worst, "tokens": n_tok, "requests": len(picked),
           "cached_requests": 0, "first_call_s": compile_s}
    if control:
        res["control_gap"] = worst_ctl
    return res
