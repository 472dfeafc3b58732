"""A run with the timed path broken underneath must come out not correct.

Each test drives the whole of a run (``run.run_cell``) on the CPU at a
toy size, without the look for a chip, once sound and once with a fault
planted in the engine after its warm-up:

* a round that leaves its state unchanged (the arena the round wrote is
  thrown away, so no key or value lands after warm-up);
* half of every dispatch's rows left out (frozen: they emit their last
  token again instead of decoding);
* a token altered where it is produced (the first decode step's tokens
  of every row are off by one).

The exchange between chips does not exist on one chip.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import copy
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run as bench_run  # noqa: E402
from benchlib import spec  # noqa: E402

TINY = spec.load_json(BENCH / "tests" / "tiny.json")
MIX = {"arrival": {"process": "backlog", "count": 24},
       "turns": {"dist": "fixed", "value": 1},
       "user_tokens": {"dist": "uniform", "min": 16, "max": 64},
       "reply_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                        "min": 8, "max": 48}}
SESSIONS = spec.load_json(BENCH / "tests" / "tiny_sessions.json")

# a full batch of 16 rows whose replies each run past 800 tokens
FULL = copy.deepcopy(TINY)
FULL["deployment"].update(capacity=16, num_pages=1152, max_context=1088)
LONG = {"arrival": {"process": "backlog", "count": 24},
        "turns": {"dist": "fixed", "value": 1},
        "user_tokens": {"dist": "uniform", "min": 16, "max": 64},
        "reply_tokens": {"dist": "uniform", "min": 801, "max": 1000}}


OFFLINE = "qwen3-14b-11l.offline-decode"
MIXES = {"offline": MIX, "sessions": SESSIONS}


def _run(mix, fault=None, cell=OFFLINE, seed=5, config=TINY):
    out, extra = bench_run.run_cell(cell, seed, 2.0, False,
                                    require_tpu=False, config=config,
                                    mix=mix, fault=fault)
    return out, extra


def state_unchanged(engine):
    adopt = engine.pool.adopt

    def keep_arena(cache):
        old = engine.pool.arena
        adopt(cache)
        engine.pool.arena = old
    engine.pool.adopt = keep_arena


def half_batch(engine):
    rnd = engine._bucket_round

    def half(params, view, rows, last_tok, frozen, *a, **k):
        drop = rows[rows.shape[0] // 2:]
        frozen = frozen.at[drop].set(True, mode="drop")
        return rnd(params, view, rows, last_tok, frozen, *a, **k)
    engine._bucket_round = half


def token_altered(engine):
    rnd = engine._bucket_round
    vocab = engine.model.cfg.vocab_size

    def alter(*a, **k):
        cache, tok, toks, pf = rnd(*a, **k)
        return cache, tok, toks.at[0].set((toks[0] + 1) % vocab), pf
    engine._bucket_round = alter


def test_sound_run_is_correct():
    out, extra = _run(MIX)
    assert out["correct"], out["compared"]
    assert extra["res"]["tokens"] >= 100
    assert list(out)[-1] == "compared"


def test_sound_sessions_compare_cached_prompts():
    out, extra = _run(SESSIONS, seed=9)
    assert out["correct"], out["compared"]
    assert extra["res"]["cached_requests"] >= 2


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
def test_fault_is_not_correct(fault, mix):
    out, _ = _run(MIXES[mix], fault=fault)
    assert not out["correct"], (fault.__name__, mix, out["compared"])


@pytest.mark.parametrize("fault", [None, half_batch])
def test_full_batch_of_long_replies(fault):
    """Every row live in the window is compared, so half of a full batch
    left out reads not correct even where one row alone streams more
    than ``check.SAMPLE_TOKENS`` tokens."""
    out, extra = _run(LONG, fault=fault, config=FULL)
    assert extra["res"]["requests"] >= FULL["deployment"]["capacity"]
    assert out["correct"] is (fault is None), out["compared"]
