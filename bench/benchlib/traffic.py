"""The one traffic generator: sessions of turns, from a mix's parameters.

Every mix is sessions. A session arrives, picks one of the shared system
prompts (or none), and runs its turns: each turn's prompt is the system
prompt, every earlier user turn and reply, and a new user turn; the
turn is due on arrival (the first) or a think time after the previous
reply's last token (the others). A single-request mix is sessions of one
turn. Arrivals are a ``backlog`` (every session due at once, offline) or
``poisson`` at ``rate_per_s``.

Sizes come from fixed quantile grids of each distribution, shuffled by
one fixed stream (``ORDER_SEED``) that does not depend on ``--seed``:
every run serves the same lengths, gaps, turns, think times and system
prompt picks in the same order, and ``--seed`` draws only the token ids
(and the weights). A window holds too few requests for a per-seed order
to average out, so an order drawn from the seed would change the work.

Distributions (``{"dist": ...}``): ``uniform`` (min, max), ``lognormal``
(median, sigma, clipped to min, max), ``exponential`` (mean), ``fixed``
(value).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List, Optional

import numpy as np

ORDER_SEED = 20261016     # the one order of sizes and times, every run


def _quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, float(dist["value"]))
    if kind == "uniform":
        return dist["min"] + q * (dist["max"] - dist["min"])
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        out = dist["median"] * np.exp(dist["sigma"] * z)
        return np.clip(out, dist.get("min", 0), dist.get("max", np.inf))
    if kind == "exponential":
        return -dist["mean"] * np.log1p(-q)
    raise ValueError(f"unknown distribution {kind!r}")


def draw_sizes(dist: Dict[str, Any], n: int, rng) -> np.ndarray:
    return rng.permutation(_quantiles(dist, n))


def draw_ints(dist: Dict[str, Any], n: int, rng) -> np.ndarray:
    return np.rint(draw_sizes(dist, n, rng)).astype(np.int64)


@dataclasses.dataclass
class Session:
    sid: int
    arrival_s: float                 # offset from the start of traffic
    system: Optional[int]            # index of the shared system prompt
    users: List[np.ndarray]          # user-turn token ids, one per turn
    replies: List[int]               # reply lengths (max_new_tokens)
    think_s: List[float]             # before turns 2.. (len = turns - 1)


@dataclasses.dataclass
class Schedule:
    system_prompts: List[np.ndarray]
    sessions: List[Session]

    def max_context(self) -> int:
        best = 0
        for s in self.sessions:
            n = (len(self.system_prompts[s.system])
                 if s.system is not None else 0)
            for u, r in zip(s.users, s.replies):
                n += len(u) + r
            best = max(best, n)
        return best


def horizon_s(mix: Dict[str, Any], seconds: float) -> float:
    """How long arrivals run: the pre-roll, the window, and a tail that
    keeps the load on while the window's last requests finish."""
    arr = mix["arrival"]
    return arr.get("preroll_s", 0.0) + seconds + arr.get("tail_s", 0.0)


def generate(mix: Dict[str, Any], seed: int, vocab: int,
             seconds: float) -> Schedule:
    tok = np.random.default_rng(seed)                   # token ids
    rng = np.random.default_rng(ORDER_SEED)             # sizes and times
    arr = mix["arrival"]
    if arr["process"] == "backlog":
        n = int(arr["count"])
        arrivals = np.zeros(n)
    elif arr["process"] == "poisson":
        n = max(1, math.ceil(arr["rate_per_s"] * horizon_s(mix, seconds)))
        gaps = draw_sizes({"dist": "exponential",
                           "mean": 1.0 / arr["rate_per_s"]}, n, rng)
        arrivals = np.cumsum(gaps)
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")

    sp = mix.get("system_prompts", {"count": 0})
    systems = [tok.integers(0, vocab, int(sp["tokens"]), dtype=np.int32)
               for _ in range(int(sp["count"]))]
    pick = (rng.permutation(np.arange(n) % len(systems)) if systems
            else [None] * n)
    turns = draw_ints(mix["turns"], n, rng)
    slots = int(turns.sum())
    users = iter(draw_ints(mix["user_tokens"], slots, rng))
    replies = iter(draw_ints(mix["reply_tokens"], slots, rng))
    thinks = iter(draw_sizes(mix["think_s"], max(slots - n, 1), rng)
                  if "think_s" in mix else [])
    sessions = []
    for i in range(n):
        t = int(turns[i])
        sessions.append(Session(
            sid=i, arrival_s=float(arrivals[i]),
            system=None if pick[i] is None else int(pick[i]),
            users=[tok.integers(0, vocab, int(next(users)), dtype=np.int32)
                   for _ in range(t)],
            replies=[int(next(replies)) for _ in range(t)],
            think_s=[float(next(thinks)) for _ in range(t - 1)]))
    return Schedule(systems, sessions)


def warmup_prompts(rng, vocab: int, n: int, length: int) -> List[np.ndarray]:
    """Throw-away prompts for warm-up (their own stream of the seed, so
    they share no prefix with the cell's traffic)."""
    return [rng.integers(0, vocab, length, dtype=np.int32) for _ in range(n)]
