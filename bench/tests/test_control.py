"""The control — the reference computed in fp8, the step below the
configuration's bf16 — must fail the served-gap limit that sound runs
pass.

At a toy size on the CPU, over three seeds: a whole run (``run_cell``)
serves its window, the reference reads the served tokens' gaps, and the
fp8 reference, put in the program's place, reads the gap of the token
it puts first at each of the same positions. The sound reading stays
under the configuration's limit; the control's goes over it. On the
chip the same readings are taken at the cells' own sizes by
``bench/calibrate.py``.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import run as bench_run  # noqa: E402
from test_faults import MIX, TINY  # noqa: E402


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_fp8_control_fails_the_limit(seed):
    out, extra = bench_run.run_cell(
        "qwen3-14b-11l.offline-decode", seed, 2.0, False, control=True,
        require_tpu=False, config=TINY, mix=MIX)
    limit = TINY["check"]["served_gap"]
    assert extra["res"]["served_gap"] <= limit
    assert extra["res"]["control_gap"] > limit, extra["res"]
