"""Paper Figures 1-3 + Table 5: primitive ops/sec vs concurrency.

Simulated Tesla (GTX295) and Fermi (GTX580) sweeps of every implementation
the paper compares:

  Figure 1 (barrier):   two-stage atomic counter vs XF flag barrier
  Figure 2 (mutex):     spin, spin+backoff, FA(+backoff)
  Figure 3 (semaphore): spin, spin+backoff, sleeping x initial value

plus the 'Host' row measured with real threads (hostbench), the Table-5
best-implementation auto-selection check, and the per-primitive
per-backend plan latency of the unified ``repro.sync`` surface (host
threading vs Pallas-interpret kernel vs pure-jnp ref).

``--smoke`` runs the backend-latency + selection sections only and
writes ``BENCH_primitives.json`` so CI records the primitives' perf
trajectory alongside ``BENCH_serve.json``.
"""

from __future__ import annotations

import argparse
import json
import threading
import time
from typing import Dict, List, Tuple

import numpy as np

from repro.core.abstraction import (FERMI, TESLA, PrimitiveKind, classify,
                                    select_impl)
from repro.core.primitives_sim import run_primitive

# Block counts swept (paper: 1..240 Tesla / 1..128 Fermi; we subsample).
TESLA_BLOCKS = (1, 8, 30, 60, 120, 240)
FERMI_BLOCKS = (1, 8, 16, 32, 64, 128)

# The paper truncates the Tesla spin-lock curves past ~120-130 blocks
# ("unpredictable and poor"); the simulator reproduces that regime, so we
# apply the same cap + a smaller event budget there.
SPIN_CAP_TESLA = 120


def _fmt(rows, name, us, detail):
    rows.append(f"{name},{us:.1f},{detail}")


def sweep(machine, name, blocks_list, *, ops=20) -> List[str]:
    rows: List[str] = []

    # ---- Figure 1: barriers
    for impl in ("atomic", "xf"):
        for nb in blocks_list:
            t0 = time.perf_counter()
            r = run_primitive(machine, "barrier", impl, blocks=nb, ops=ops)
            us = (time.perf_counter() - t0) * 1e6
            _fmt(rows, f"fig1_{name}_barrier_{impl}_b{nb}", us,
                 f"ops_per_s={r.ops_per_sec:.0f}"
                 f"{';TRUNC' if r.truncated else ''}")

    # ---- Figure 2: mutexes
    for impl in ("spin", "spin_backoff", "fa"):
        for nb in blocks_list:
            if name == "tesla" and impl == "spin" and nb > SPIN_CAP_TESLA:
                continue
            t0 = time.perf_counter()
            r = run_primitive(machine, "mutex", impl, blocks=nb, ops=ops,
                              max_events=8_000_000)
            us = (time.perf_counter() - t0) * 1e6
            _fmt(rows, f"fig2_{name}_mutex_{impl}_b{nb}", us,
                 f"ops_per_s={r.ops_per_sec:.0f};fair={int(r.fair_fifo)};"
                 f"viol={r.violations}{';TRUNC' if r.truncated else ''}")

    # ---- Figure 3: semaphores x initial value
    for init in (1, 2, 10, 120):
        for impl in ("spin", "spin_backoff", "sleeping"):
            for nb in blocks_list:
                if name == "tesla" and impl.startswith("spin") \
                        and nb > SPIN_CAP_TESLA:
                    continue
                t0 = time.perf_counter()
                r = run_primitive(machine, "semaphore", impl, blocks=nb,
                                  ops=min(ops, 10), initial=init,
                                  max_events=5_000_000)
                us = (time.perf_counter() - t0) * 1e6
                _fmt(rows, f"fig3_{name}_sem{init}_{impl}_b{nb}", us,
                     f"ops_per_s={r.ops_per_sec:.0f};viol={r.violations}"
                     f"{';TRUNC' if r.truncated else ''}")
    return rows


def table5_check() -> List[str]:
    """Auto-selection (select_impl) vs the paper's Table 5."""
    rows: List[str] = []
    expected = {
        ("tesla", "barrier"): "xf",
        ("fermi", "barrier"): "xf",
        ("tesla", "mutex"): "fa",
        ("fermi", "mutex"): "spin_backoff",
        ("tesla", "sem_low"): "sleeping",
        ("fermi", "sem_low"): "spin_backoff",
        ("tesla", "sem_high"): "sleeping",
        ("fermi", "sem_high"): "sleeping",
    }
    t0 = time.perf_counter()
    got = {
        ("tesla", "barrier"): select_impl(TESLA, PrimitiveKind.BARRIER).algorithm,
        ("fermi", "barrier"): select_impl(FERMI, PrimitiveKind.BARRIER).algorithm,
        ("tesla", "mutex"): select_impl(TESLA, PrimitiveKind.MUTEX).algorithm,
        ("fermi", "mutex"): select_impl(FERMI, PrimitiveKind.MUTEX).algorithm,
        ("tesla", "sem_low"): select_impl(
            TESLA, PrimitiveKind.SEMAPHORE, semaphore_initial=1).algorithm,
        ("fermi", "sem_low"): select_impl(
            FERMI, PrimitiveKind.SEMAPHORE, semaphore_initial=1).algorithm,
        ("tesla", "sem_high"): select_impl(
            TESLA, PrimitiveKind.SEMAPHORE, semaphore_initial=120).algorithm,
        ("fermi", "sem_high"): select_impl(
            FERMI, PrimitiveKind.SEMAPHORE, semaphore_initial=120).algorithm,
    }
    us = (time.perf_counter() - t0) * 1e6
    n_match = sum(got[k] == expected[k] for k in expected)
    detail = ";".join(f"{k[0]}.{k[1]}={got[k]}" +
                      ("" if got[k] == expected[k] else f"(paper:{expected[k]})")
                      for k in expected)
    rows.append(f"table5_selection,{us:.1f},match={n_match}/8;{detail}")
    rows.append(f"table5_classes,{0.0:.1f},"
                f"tesla={classify(TESLA)};fermi={classify(FERMI)}")
    return rows


def headline_speedups(ops: int = 20) -> List[str]:
    """Paper Section 7 headline numbers."""
    rows: List[str] = []
    t0 = time.perf_counter()
    tes_spin = run_primitive(TESLA, "mutex", "spin", blocks=120, ops=ops,
                             max_events=8_000_000)
    tes_fa = run_primitive(TESLA, "mutex", "fa", blocks=240, ops=ops)
    fer_spin = run_primitive(FERMI, "mutex", "spin", blocks=128, ops=ops)
    fer_bo = run_primitive(FERMI, "mutex", "spin_backoff", blocks=128, ops=ops)
    fer_sem_spin = run_primitive(FERMI, "semaphore", "spin", blocks=128,
                                 ops=10, initial=120, max_events=5_000_000)
    fer_sem_slp = run_primitive(FERMI, "semaphore", "sleeping", blocks=128,
                                ops=10, initial=120)
    tes_sem_spin = run_primitive(TESLA, "semaphore", "spin_backoff",
                                 blocks=120, ops=10, initial=10,
                                 max_events=5_000_000)
    tes_sem_slp = run_primitive(TESLA, "semaphore", "sleeping", blocks=120,
                                ops=10, initial=10)
    us = (time.perf_counter() - t0) * 1e6
    rows.append(
        f"headline_fa_vs_spin_tesla,{us:.1f},"
        f"x={tes_fa.ops_per_sec / tes_spin.ops_per_sec:.1f};paper=40")
    rows.append(
        f"headline_backoff_gain_fermi,{0.0:.1f},"
        f"pct={100 * (fer_bo.ops_per_sec / fer_spin.ops_per_sec - 1):.0f};paper=40")
    rows.append(
        f"headline_sleepsem_vs_spin_fermi,{0.0:.1f},"
        f"x={fer_sem_slp.ops_per_sec / fer_sem_spin.ops_per_sec:.1f};paper=70")
    rows.append(
        f"headline_sleepsem_vs_spin_tesla,{0.0:.1f},"
        f"x={tes_sem_slp.ops_per_sec / tes_sem_spin.ops_per_sec:.1f};paper=3")
    return rows


def backend_latency_rows(
    *, n: int = 12, capacity: int = 3, repeats: int = 3,
    backends: Tuple[str, ...] = ("host", "kernel", "ref"),
) -> Tuple[List[str], Dict[str, Dict[str, float]]]:
    """Per-primitive per-backend plan latency of the unified sync API.

    The kernel/ref numbers are the post-compile cost of one plan on a
    warm compile cache; the host number is the cost of
    an *observed execution* with real threads (the equivalence oracle,
    never on a hot loop)."""
    from repro.sync import SyncLibrary
    lib = SyncLibrary.host_default()
    rng = np.random.default_rng(0)
    arrivals = np.sort(rng.uniform(0, 3, n)).astype(np.float32)
    holds = rng.uniform(1, 3, n).astype(np.float32)
    arrival_perm = rng.permutation(n).astype(np.int32)
    present = np.ones(n, np.int64)

    plans = {
        "semaphore": lambda be: lib.plan_semaphore(
            arrivals, holds, capacity, backend=be),
        "mutex": lambda be: lib.plan_mutex(arrival_perm, backend=be),
        "barrier": lambda be: lib.plan_barrier(
            present, epoch=1, backend=be),
    }
    rows: List[str] = []
    data: Dict[str, Dict[str, float]] = {}
    for prim, plan in plans.items():
        data[prim] = {}
        for be in backends:
            plan(be)  # warm (compile for the jitted backends)
            times = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                plan(be)
                times.append((time.perf_counter() - t0) * 1e6)
            us = float(np.median(times))
            data[prim][be] = us
            rows.append(f"sync_{prim}_{be},{us:.1f},n={n};plan_latency")
    return rows, data


def alloc_sweep(
    out: str, *, num_pages: int = 192, page_size: int = 8,
    strategies: Tuple[str, ...] = ("spin", "spin_backoff", "sleeping",
                                   "adaptive"),
    threads_list: Tuple[int, ...] = (1, 2, 4, 8),
    ops_per_thread: int = 400,
) -> List[str]:
    """Spin vs spin_backoff vs sleeping vs adaptive on the REAL
    ``PagePool`` hot loop (not a simulator): every thread churns batched
    alloc/free requests against one pool, so the guarding ticket lock
    sees exactly the serving allocator's access pattern. Thread count is
    the contention level; the adaptive arm re-tunes its wait strategy
    from the measured contended-acquire window between its own
    operations (the between-rounds contract). Writes ``out``
    (BENCH_alloc.json): per-strategy per-thread-count ops/s, contended
    fraction, held time, and the strategy the adaptive arm settled on.
    """
    from repro.serve.kv_pages import PagePool, PagePoolExhausted
    from repro.sync import SyncLibrary

    lib = SyncLibrary.host_default()
    rows: List[str] = []
    data: Dict[str, Dict[str, dict]] = {}
    for strat in strategies:
        data[strat] = {}
        for nt in threads_list:
            if strat == "spin" and nt > 2:
                # raw spin under real contention starves the lock holder
                # (same regime the paper truncates the Tesla spin curves
                # in: "unpredictable and poor") — record the truncation
                # instead of burning minutes measuring it
                rows.append(f"alloc_{strat}_t{nt},0.0,TRUNC")
                data[strat][str(nt)] = {"truncated": True}
                continue
            pool = PagePool(num_pages, page_size, sync=lib,
                            wait_mode=strat)
            start = threading.Barrier(nt + 1)

            def worker(tid, pool=pool, start=start, nt=nt, strat=strat):
                rng = np.random.default_rng(tid)
                held: List[np.ndarray] = []
                start.wait()
                for i in range(ops_per_thread):
                    if strat == "adaptive" and tid == 0 and i % 32 == 31:
                        pool.retune()      # between ops, never while held
                    n = int(rng.integers(1, 4))
                    # keep the pool near-full so waiting really happens
                    if held and (len(held) > 6
                                 or pool.n_free < 4 * nt):
                        pool.free_batch([held.pop(rng.integers(len(held)))])
                    try:
                        ids = pool.alloc_batch([n], [tid])[0]
                        held.append(ids)
                    except PagePoolExhausted:
                        pass               # exhausted: free next iteration
                if held:
                    pool.free_batch(held)

            threads = [threading.Thread(target=worker, args=(t,))
                       for t in range(nt)]
            for t in threads:
                t.start()
            start.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            pool.check()
            st = pool.lock_stats()
            got = {
                "wall_s": dt,
                "lock_acquires": int(st["acquires"]),
                "acquires_per_s": st["acquires"] / dt if dt else 0.0,
                "contended_fraction": (st["contended_acquires"]
                                       / max(st["acquires"], 1)),
                "held_s": st["held_s"],
                "strategy_final": st["strategy"],
                "retunes": int(st.get("retunes", 0)),
            }
            data[strat][str(nt)] = got
            rows.append(
                f"alloc_{strat}_t{nt},{dt * 1e6:.1f},"
                f"acq_per_s={got['acquires_per_s']:.0f};"
                f"contended={got['contended_fraction']:.2f};"
                f"final={got['strategy_final']}")
    blob = {"num_pages": num_pages, "page_size": page_size,
            "ops_per_thread": ops_per_thread, "arms": data}
    with open(out, "w") as f:
        json.dump(blob, f, indent=2)
    rows.append(f"# wrote {out}")
    return rows


def main(fast: bool = True) -> List[str]:
    blocks_t = TESLA_BLOCKS if not fast else (1, 30, 120, 240)
    blocks_f = FERMI_BLOCKS if not fast else (1, 32, 128)
    rows = sweep(TESLA, "tesla", blocks_t)
    rows += sweep(FERMI, "fermi", blocks_f)
    rows += table5_check()
    rows += headline_speedups()
    rows += backend_latency_rows()[0]
    return rows


def smoke(out: str) -> List[str]:
    """CI tier: backend latencies + selection check -> JSON artifact."""
    rows, backends = backend_latency_rows()
    t5 = table5_check()
    rows += t5
    blob = {
        "backends_plan_latency_us": backends,
        "table5": t5[0].split(",", 2)[2],
        "machine_classes": t5[1].split(",", 2)[2],
    }
    with open(out, "w") as f:
        json.dump(blob, f, indent=2)
    rows.append(f"# wrote {out}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="backend-latency + selection sections only; "
                         "write the JSON artifact")
    ap.add_argument("--alloc-sweep", action="store_true",
                    help="wait-strategy sweep on the real PagePool hot "
                         "loop; writes the BENCH_alloc.json artifact")
    ap.add_argument("--out", default="BENCH_primitives.json")
    ap.add_argument("--alloc-out", default="BENCH_alloc.json")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.alloc_sweep:
        for r in alloc_sweep(args.alloc_out):
            print(r)
    elif args.smoke:
        for r in smoke(args.out):
            print(r)
    else:
        for r in main(fast=False):
            print(r)
