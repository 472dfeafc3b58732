"""The trace reduction, checked by hand on known events and on a small
trace recorded on a v5e (``data/trace_small.json``: the first 200 ms of
a traced window of ``qwen3-14b-12l.offline-decode``, as
``benchlib.trace.extract`` read it)."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import trace  # noqa: E402

DATA = BENCH / "tests" / "data" / "trace_small.json"


def test_reduce_by_hand():
    ms = 1_000_000
    ev = {
        "host": [("window", 0, 100 * ms), ("round", 10 * ms, 60 * ms),
                 ("hook", 75 * ms, 10 * ms)],
        "modules": [("jit__bucket_round_impl(12)", 20 * ms, 40 * ms),
                    ("jit_semaphore_admission(3)", 12 * ms, 2 * ms),
                    ("jit__bucket_round_impl(12)", 95 * ms, 10 * ms)],
        "ops": [("%while.3 = (s32[]) while(...)", 20 * ms, 40 * ms),
                ("%fusion.1 = bf16[4,8]{1,0} fusion(...)", 20 * ms, 25 * ms),
                ("%fusion.2 = f32[2]{0} fusion(...)", 45 * ms, 15 * ms),
                ("%semaphore_admission.1 = (f32[1,128]) custom-call(...)",
                 12 * ms, 2 * ms),
                ("%fusion.1 = bf16[4,8]{1,0} fusion(...)", 95 * ms, 10 * ms)],
    }
    r = trace.reduce(ev)
    assert r["window_s"] == pytest.approx(0.1)
    # busy: [12,14) + [20,60) + [95,100) = 47 ms
    assert r["busy_s"] == pytest.approx(0.047)
    assert r["round_device_s"] == pytest.approx(0.045)
    assert r["planner_device_s"] == pytest.approx(0.002)
    # self time: the while's [20,60) less its body ops [20,45) + [45,60);
    # the last fusion.1 is cut at the window's end
    assert r["device_ops"][0] == ["%fusion.1 bf16[4,8]", pytest.approx(0.030)]
    assert ["%while.3 (s32[])", pytest.approx(0.0)] in r["device_ops"]
    # gaps: [0,12) none, [14,20) round, [60,95) — middle 77.5 in hook
    assert r["idle_gaps"][0] == ["hook", pytest.approx(0.035)]
    assert r["idle_gaps"][1] == ["none", pytest.approx(0.012)]
    assert r["idle_gaps"][2] == ["round", pytest.approx(0.006)]


def test_reduce_recorded_v5e_trace():
    ev = {k: [tuple(e) for e in v]
          for k, v in json.loads(DATA.read_text()).items()}
    r = trace.reduce(ev)
    win = [e for e in ev["host"] if e[0] == "window"][0]
    lo, hi = win[1], win[1] + win[2]
    # by hand: merge the op intervals inside the window
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in ev["ops"]
                if s + d > lo and s < hi)
    busy, end = 0, lo
    for s, e in iv:
        s = max(s, end)
        if e > s:
            busy += e - s
            end = e
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["round_device_s"] > 0          # the round programs are named
    assert r["planner_device_s"] > 0        # and so is the planner kernel
