"""Readings behind the correctness limits and the knee, in one process.

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \
        --seconds <s> [--control] [--rates r1 r2 ...] [--out file.jsonl]

Runs the cell once per seed (and per arrival rate, where ``--rates``
overrides the mix's ``rate_per_s``: the sweep that finds the knee),
each a whole run of ``run.run_cell`` with its own weights and engine,
and writes one JSON line per run: the served-token gap, with
``--control`` the gap of the token the fp8 reference puts first at the
same positions, the end-to-end metrics, the set-up parts and the
compared numbers. The benchmark's own runs never compute the control.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run as bench_run  # noqa: E402
from benchlib import spec  # noqa: E402
from benchlib.readings import pct  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", type=float, nargs="*", default=None)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    cell = spec.workload(a.workload)
    base = spec.traffic(cell["traffic"])
    rates = a.rates or [None]
    sink = open(a.out, "a") if a.out else None
    for rate in rates:
        mix = copy.deepcopy(base)
        if rate is not None:
            mix["arrival"]["rate_per_s"] = rate
        for seed in a.seeds:
            out, extra = bench_run.run_cell(
                a.workload, seed, a.seconds, False, control=a.control,
                mix=mix)
            res = extra["res"]
            due = [r for r in extra["window"].reqs if r.in_window]
            ttft = [(r.first_s - r.due_s) * 1e3 for r in due if r.n_out]
            row = {"workload": a.workload, "seed": seed, "rate": rate,
                   "seconds": a.seconds, "served_gap": res["served_gap"],
                   "control_gap": res.get("control_gap"),
                   "tokens": res["tokens"], "requests": res["requests"],
                   "cached_requests": res["cached_requests"],
                   "correct": out["correct"], "attempted": out["attempted"],
                   "failed": out["failed"], "metrics": out["metrics"],
                   "parts": extra["parts"],
                   "due": len(due), "answered": len(ttft),
                   "ttft_ms_p50_p90_max": [pct(ttft, 50), pct(ttft, 90),
                                           max(ttft, default=None)],
                   "peak": out["device"]["memory_peak_bytes"]}
            line = json.dumps(row)
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
            del out, extra
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
