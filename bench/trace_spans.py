"""One traced run of a cell, read for the program's own spans and scope.

    python bench/trace_spans.py --workload <cell> --seed <n> --seconds <s>
        [--keep <dir>] [--save-events <json> --ms 200]

Runs the cell once with ``--trace 1`` as ``bench/run.py`` does and keeps
the raw trace, then reads it again with ``benchlib/spans.py``: the
serving program's host spans (``serve.*``, ``frontend.*``) and the
device ops in its ``paged_attention`` name scope. Prints the run's
result line, then one JSON object with the readings of the metrics that
need those spans (``bench/metrics/<name>.py``), the device's idle time
split over the innermost span covering it, the longest idle gaps so
named, and two cross-checks: a traced round (``serve.step`` plus the gap
before the next) against ``round_ms.offline``, and the scoped ops'
device time against the round programs'.

``--save-events`` writes the extracted events of the first ``--ms``
milliseconds of the traced window as JSON (ops by short name, with their
scope flag), for the hand-checked test data.
"""

from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run as run_mod  # noqa: E402  (starts the set-up clock)

#: Metrics read from the program's spans, scope and counters.
METRICS = ("round_host_ms.offline", "plan_ms.offline",
           "round_gap_ms.offline", "attention_share.offline",
           "attention_roofline.offline", "lane_use_share.offline")


def save_events(ev, path: str, ms: float) -> None:
    from benchlib import trace
    lo = [e for e in ev["host"] if e[0] == "window"][0][1]
    hi = lo + int(ms * 1e6)
    keep = {"ops": [[trace.short_name(n), s, d, int(f)]
                    for n, s, d, f in ev["ops"] if lo <= s < hi],
            "modules": [list(e) for e in ev["modules"] if lo <= e[1] < hi],
            "host": [list(e) for e in ev["host"]
                     if e[0] != "window" and lo <= e[1] < hi]}
    keep["host"].append(["window", lo, hi - lo])
    with open(path, "w") as f:
        json.dump(keep, f)
    print(f"saved {sum(map(len, keep.values()))} events to {path}",
          file=sys.stderr)


def read(out, extra, trace_dir: str, config, *, save_path=None,
         ms: float = 200.0):
    """The readings of a traced run (``run.run_cell`` with
    ``keep_trace=trace_dir``) that need the program's spans."""
    from benchlib import device, readings, spec, spans, trace
    ev = spans.extract(trace.find_xplane(trace_dir))
    if save_path:
        save_events(ev, save_path, ms)
    red = dict(extra["trace"] or {})
    red.update(spans.reduce(ev))
    pk = device.PEAKS.get(out["device"]["kind"],
                          device.PEAKS["TPU v5 lite"])
    run = readings.Run(config=config, window=extra["window"], setup_s=0.0,
                       peaks=pk, trace=red)
    steps, gaps = red.get("steps", []), red.get("step_gaps_s", [])
    per_round = [(st[0] + g) * 1e3 for st, g in zip(steps, gaps)]
    idle = red.get("idle_in_span", {})
    total_idle = sum(idle.values())

    def quartiles(vals):
        return statistics.quantiles(vals, n=4) if len(vals) > 1 else None

    return {
        "metrics": {m: spec.metric_reader(m).read(run) for m in METRICS},
        "round_ms.offline": readings.round_interval_ms(run),
        "traced_round_ms_median": (statistics.median(per_round)
                                   if per_round else None),
        "traced_rounds": len(steps),
        "traced_decode_positions": sum(
            (r.out_tokens - r.first_tokens) * r.ctx_decoding / r.n_decoding
            for r in run.traced_rounds() if r.n_decoding),
        "program_spans": sorted({e[0] for e in ev["host"]}
                                & set(spans.PROGRAM_SPANS)),
        "scoped_ops": sum(1 for e in ev["ops"] if e[3]),
        "scoped_device_s": red.get("scoped_device_s"),
        "round_device_s": red.get("round_device_s"),
        "busy_s": red.get("busy_s"), "window_s": red.get("window_s"),
        "idle_in_span": idle,
        "idle_in_span_share": ({k: v / total_idle for k, v in idle.items()}
                               if total_idle else {}),
        "idle_gaps": red.get("idle_gaps"),
        "step_ms_quartiles": quartiles([s[0] * 1e3 for s in steps]),
        "wait_ms_quartiles": quartiles([s[1] * 1e3 for s in steps]),
        "plan_ms_quartiles": quartiles([s[2] * 1e3 for s in steps]),
        "gap_ms_quartiles": quartiles([g * 1e3 for g in gaps]),
        "device_ops": red.get("device_ops"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=str(run_mod.ROOT / "artifacts"
                                          / "trace_spans"))
    ap.add_argument("--save-events", default=None)
    ap.add_argument("--ms", type=float, default=200.0)
    a = ap.parse_args(argv)
    shutil.rmtree(a.keep, ignore_errors=True)
    out, extra = run_mod.run_cell(a.workload, a.seed, a.seconds, True,
                                  keep_trace=a.keep)
    print(json.dumps(out), flush=True)
    from benchlib import spec
    config = spec.config(spec.workload(a.workload)["config"])
    print(json.dumps(read(out, extra, a.keep, config,
                          save_path=a.save_events, ms=a.ms)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
