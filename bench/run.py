"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``). The run draws the weights on the
device from ``--seed``, builds the paged serving engine through the
program's own ``make_engine``, warms up every program the window can
dispatch, drives the mix through the asyncio front-end for ``--seconds``
and checks a seeded sample of what it served against the plain
reference. With ``--trace 1`` it traces part of the window and reports
the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), then ``compared``: each number the correctness
check compared, with its limit. The same numbers end standard error.
Without a TPU, or with fewer chips than the cell asks for, the run
exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchlib import spec  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def seed_key(jax, seed: int):
    """A PRNG key from any whole number (seeds may pass 2^31)."""
    import numpy as np
    a, b = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(int(a) >> 1), int(b) >> 1)


def latency_line(due, run) -> str:
    """TTFT and time per output token of the requests due in the window,
    as median, 90th percentile and mean (ms), beside the count."""
    from benchlib.readings import pct
    ttft = [(r.first_s - r.due_s if r.n_out else run.missing_s(r)) * 1e3
            for r in due]
    tpot = [(r.last_s - r.first_s) / (r.n_out - 1) * 1e3
            for r in due if r.state == "finished" and r.n_out >= 2]
    parts = [f"{len(due)} due"]
    for label, vals in (("ttft", ttft), ("tpot", tpot)):
        if vals:
            parts.append(f"{label} ms p50 {pct(vals, 50):.2f} p90 "
                         f"{pct(vals, 90):.2f} mean {sum(vals) / len(vals):.2f}")
    return "latency: " + "; ".join(parts)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             control: bool = False, require_tpu: bool = True,
             config=None, mix=None, fault=None,
             keep_trace=None):
    """One run of a cell; returns (result dict, extra readings). The
    keyword arguments serve the calibration tool and the tests: a config
    or mix in place of the cell's files, the fp8 control's reading, a
    run without the look for a chip, a fault planted in the engine."""
    bench = spec.benchmark()
    cell = spec.workload(name, bench)
    c = config or spec.config(cell["config"])
    mix = mix or spec.traffic(cell["traffic"])
    import jax
    from benchlib import check, device, drive, readings, trace as trace_mod
    from benchlib import traffic

    dev = device.describe(jax, cell["chips"], require_tpu)
    pk = device.peaks(dev["kind"]) if require_tpu else device.PEAKS[
        "TPU v5 lite"]
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device {dev}; compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = drive.CompileCounter(jax)
    parts = {"backend_s": time.perf_counter() - T_START}

    t = time.perf_counter()
    ref = spec.reference(c["reference"])
    w = ref.draw(c, seed_key(jax, seed))
    jax.block_until_ready(w)
    parts["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    engine = drive.build_engine(c, ref.to_program(c, w), seed)
    n0 = compiles.n
    rounds = drive.warm_up(engine, c["vocab_size"], seed)
    parts["warmup_s"] = time.perf_counter() - t
    log(f"warm-up: {rounds} rounds, {compiles.n - n0} compiles or "
        f"cache loads")
    if fault is not None:
        fault(engine)

    sched = traffic.generate(mix, seed, c["vocab_size"], seconds)
    if sched.max_context() > c["deployment"]["max_context"]:
        raise SystemExit(f"[bench] the mix reaches {sched.max_context()} "
                         f"tokens, over the configuration's max_context")
    tdir = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tdir = str(TRACE_DIR)
    t = time.perf_counter()
    win = drive.drive(engine, sched, mix, seconds, jax, trace_dir=tdir,
                      compiles=compiles,
                      intake_limit=c["deployment"].get("intake_limit", 1024))
    parts["fill_s"] = win.t_open - t
    setup_s = win.t_open - T_START
    peak = device.peak_bytes(jax)
    dev["memory_peak_bytes"] = peak
    cap = engine.capacity
    chunk = engine.prefill_chunk
    st = win.stats_close
    log("set-up " + ", ".join(f"{k} {v:.3f}" for k, v in parts.items())
        + f"; setup_s {setup_s:.3f}")
    late = sorted(win.lateness_s)
    log(f"window {win.seconds:.3f}s: {len(win.rounds)} rounds in run, "
        f"{win.tokens_in_window} tokens streamed in window, "
        f"{win.compiles_in_window} compiles in window, generator late "
        f"p50 {1e3 * late[len(late) // 2] if late else 0:.2f} ms max "
        f"{1e3 * late[-1] if late else 0:.2f} ms; capacity {cap}; "
        f"dispatch retraces {st.get('dispatch_retraces')}; prefix hits "
        f"{st.get('prefix_hits')} cache hits {st.get('cache_hits')}; "
        f"pauses {st.get('page_pauses')} preemptions "
        f"{st.get('page_preemptions')}; peak_bytes_in_use {peak}")

    red = None
    if trace:
        t = time.perf_counter()
        path = trace_mod.find_xplane(tdir)
        red = trace_mod.reduce(trace_mod.extract(path)) if path else {}
        log(f"trace reduced in {time.perf_counter() - t:.1f}s: "
            f"{ {k: v for k, v in red.items() if k != 'device_ops'} }")
        if keep_trace:
            shutil.copytree(TRACE_DIR, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    run = readings.Run(config=c, window=win, setup_s=setup_s, peaks=pk,
                       trace=red)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_for(name, kind, bench):
        v = spec.metric_reader(m["name"]).read(run)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the program's state goes before the reference runs
    due = run.due_in_window()
    if mix["arrival"]["process"] != "backlog":
        log(latency_line(due, run))
    unanswered = sum(1 for r in due
                     if r.state != "finished" or r.n_out != r.max_new)
    if mix["arrival"]["process"] == "backlog":
        unanswered = sum(1 for r in due if r.state == "failed")
    del engine, run
    gc.collect()
    t = time.perf_counter()
    picked = check.sample(win.reqs, cap)
    res = check.compare(jax, ref, c, w, picked, control=control)
    res["cached_requests"] = sum(check.is_cached(s.req, chunk)
                                 for s in picked)
    log(f"reference check in {time.perf_counter() - t:.1f}s: "
        f"{res['requests']} requests ({res['cached_requests']} served from "
        f"cached pages), {res['tokens']} served tokens compared; first "
        f"call of each shape (compile or cache load, and run) "
        f"{res['first_call_s']:.1f}s")
    limit = c["check"]["served_gap"]
    # no token compared reads as the widest gap there can be (2)
    gap = res["served_gap"] if res["tokens"] else 2.0
    compared = {"served_gap": {"value": gap, "limit": limit},
                "unanswered": {"value": unanswered, "limit": 0}}
    correct = all(v["value"] <= v["limit"] for v in compared.values())
    out = {"correct": bool(correct), "attempted": len(due),
           "failed": unanswered, "metrics": metrics, "device": dev}
    if trace and red:
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["compared"] = compared
    return out, {"res": res, "window": win, "parts": parts, "trace": red}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw profiler trace here (to read it "
                         "by hand)")
    a = ap.parse_args(argv)
    out, _ = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                      keep_trace=a.keep_trace)
    for k, v in out["compared"].items():
        log(f"compared {k} {v['value']} limit {v['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
