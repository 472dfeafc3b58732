"""Serving driver: slot-pool continuous batching behind semaphore admission.

Drives the full serving path on a reduced config: one engine replica with
a preallocated K-slot KV arena, the paper's Algorithm-5 sleeping
semaphore as the admission gate, the Pallas semaphore kernel replanning
the grant timeline every scheduler round, and one fixed-shape batched
decode dispatch per round.

  python -m repro.launch.serve --arch qwen3-14b --smoke \
      --requests 32 --capacity 8 --new-tokens 16

``--legacy`` runs the old per-request Python decode loop on the same
workload for comparison. ``--kv-layout paged`` swaps the contiguous slot
arena for the block-table page arena (serve/kv_pages.py) whose
mutex-gated allocator lets per-slot contexts exceed ``max_len`` at equal
arena bytes; ``--page-size`` sets its granularity and
``--prefix-sharing`` adds copy-on-write prompt-prefix sharing on top
(repeated prompts adopt live pages instead of allocating).
``--prefill-chunk-tokens`` turns on continuous chunked prefill — prompts
prefill a fixed chunk per scheduler round *inside* the decode dispatch,
under a ``--round-token-budget`` that funds decode rows first — so a
long prompt never stalls in-flight decodes.
The sync substrate is a CLI knob:
``--sync-backend`` picks the admission planner's backend (interpret
kernel / TPU hardware / pure-jnp ref) and ``--admission-sem`` the live
gate's algorithm (the paper's sleeping FA semaphore vs the spin
baselines) — both flow into the engine through one injected
``SyncLibrary``.

``--open-loop`` swaps the closed-loop batch drive for production-shaped
traffic through the asyncio front-end (serve/frontend.py, DESIGN.md
§13): concurrent clients arrive as a Poisson process at
``--arrival-rate`` req/s, stream tokens as rounds complete, a
``--cancel-rate`` fraction hangs up mid-generation, ``--slo-ms`` sets
the time-to-first-token SLO that splits goodput from throughput, and
``--deadline-ms`` (optional) arms hard per-request deadlines the
scheduler enforces (queued-expire + late-row deprioritization).
``--intake-limit`` bounds the ungranted population; past it, submits
are shed explicitly.

  python -m repro.launch.serve --arch qwen3-14b --smoke --open-loop \
      --requests 32 --capacity 4 --arrival-rate 50 --cancel-rate 0.25 \
      --slo-ms 500 --kv-layout paged --prefix-sharing on
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core.abstraction import PrimitiveKind
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serve.engine import RequestState, ServeEngine, SlotServeEngine
from repro.serve.faults import FaultPlan
from repro.serve.frontend import AsyncFrontend, IntakeFullError
from repro.serve.kv_pages import PageLeakError
from repro.serve.scheduler import plan_admission
from repro.sync import SyncLibrary, get_backend


def build(args):
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if args.num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=args.num_layers)
    if cfg.is_encdec or cfg.frontend is not None:
        raise SystemExit("serve.py drives token-LM archs")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    return cfg, model, params


def make_sync_library(args) -> SyncLibrary:
    """One SyncLibrary from the CLI knobs; injected everywhere."""
    return SyncLibrary.host_default(
        backend=None if args.sync_backend == "auto" else args.sync_backend,
        semaphore_kind=(None if args.admission_sem == "auto"
                        else args.admission_sem))


def make_fault_plan(args):
    """The CLI's chaos knob: one seeded FaultPlan driving every
    transient injection site (allocator abort, dispatch exception,
    stuck holder) at ``--fault-rate``, or None when chaos is off."""
    if getattr(args, "fault_rate", 0.0) <= 0.0:
        return None
    return FaultPlan(args.fault_seed, alloc_rate=args.fault_rate,
                     dispatch_rate=args.fault_rate,
                     stuck_rate=args.fault_rate, stuck_hold_s=5e-3)


def make_engine(model, params, args, sync=None) -> SlotServeEngine:
    """One engine from the CLI knobs — shared by every driver mode."""
    max_len = args.prompt_len + args.new_tokens + 1
    fault_plan = make_fault_plan(args)
    return SlotServeEngine(
        model, params, capacity=args.capacity, max_len=max_len,
        decode_chunk=args.decode_chunk, seed=args.seed,
        kv_layout=args.kv_layout, page_size=args.page_size,
        num_pages=args.num_pages,
        page_growth=args.page_growth, allocator_wait=args.allocator_wait,
        prefix_sharing=args.prefix_sharing,
        prefix_cache=args.prefix_cache,
        cache_watermark=args.cache_watermark,
        prefill_chunk_tokens=args.prefill_chunk_tokens,
        round_token_budget=args.round_token_budget,
        attention_impl=args.attention_impl,
        bucketed_dispatch=args.bucketed_dispatch,
        fault_plan=fault_plan,
        allocator_watchdog_s=(1e-3 if fault_plan is not None else None),
        sync=sync if sync is not None else make_sync_library(args))


def enforce_leak_gate(engine) -> None:
    """Hard post-drain leak gate: smoke runs fail LOUDLY on a leak — a
    non-zero exit, not a printed number nobody reads. The prefix cache's
    held pages are intentional retention, so it is dropped first;
    whatever remains in use after a full drain is a leak."""
    if engine.kv_layout != "paged":
        return
    if engine.prefix_cache is not None:
        engine.drop_prefix_cache()
    try:
        engine.pool.check()
    except (PageLeakError, AssertionError) as e:
        print(f"[serve] FATAL: post-drain page-leak check failed: {e}")
        raise SystemExit(1)
    leaked = int(engine.pool.pages.in_use)
    if leaked:
        print(f"[serve] FATAL: {leaked} of "
              f"{engine.pool.pages.num_pages} pages leaked after "
              f"drain (free-list {engine.pool.pages.n_free})")
        raise SystemExit(1)
    print(f"[serve] post-drain leak check: OK "
          f"(0 of {engine.pool.pages.num_pages} pages leaked)")


def run_slot_engine(model, params, prompts, args, arrivals_steps=None,
                    sync=None):
    """Serve all requests through the slot engine. ``arrivals_steps``
    staggers submissions on the decode-step clock (None = burst at 0)."""
    n = len(prompts)
    engine = make_engine(model, params, args, sync)
    arrivals = (np.zeros(n) if arrivals_steps is None
                else np.asarray(arrivals_steps))
    t0 = time.perf_counter()
    nxt = 0
    while nxt < n or engine.queue or engine.active:
        while nxt < n and arrivals[nxt] <= engine.step_clock:
            engine.submit(prompts[nxt], args.new_tokens)
            nxt += 1
        if engine.step() == 0 and not engine.queue and nxt < n:
            # idle tick: nothing in flight, next arrival in the future
            engine.step_clock += 1
    dt = time.perf_counter() - t0
    return engine, dt


def run_open_loop(model, params, prompts, args, sync=None):
    """Open-loop traffic through the asyncio front-end: Poisson
    arrivals, token streaming, mid-flight cancellations, TTFT SLO.

    Returns ``(engine, wall_s, report)`` where ``report`` carries the
    open-loop ledger: per-request TTFT, goodput-under-SLO, shed and
    cancelled counts, and the post-drain page-leak check."""
    engine = make_engine(model, params, args, sync)
    rng = np.random.default_rng(args.seed)
    gaps_s = rng.exponential(1.0 / max(args.arrival_rate, 1e-9),
                             len(prompts))
    # which clients hang up, and after how many streamed tokens
    cancel_after = [
        (1 + int(rng.integers(0, max(args.new_tokens // 2, 1))))
        if rng.random() < args.cancel_rate else None
        for _ in prompts]
    deadline_s = (args.deadline_ms / 1e3
                  if args.deadline_ms is not None else None)
    results = []

    async def client(fe, i, prompt):
        rec = {"i": i, "tokens": [], "shed": False, "handle": None}
        results.append(rec)
        try:
            h = await fe.submit(prompt, args.new_tokens,
                                deadline_s=deadline_s)
        except IntakeFullError:
            rec["shed"] = True
            return
        rec["handle"] = h
        async for tok in h:
            rec["tokens"].append(tok)
            if (cancel_after[i] is not None
                    and len(rec["tokens"]) >= cancel_after[i]):
                h.cancel()

    async def drive():
        async with AsyncFrontend(engine,
                                 intake_limit=args.intake_limit) as fe:
            tasks = []
            for i, prompt in enumerate(prompts):
                await asyncio.sleep(gaps_s[i])
                tasks.append(asyncio.ensure_future(client(fe, i, prompt)))
            await asyncio.gather(*tasks)
            await fe.drain()
            return fe.stats()

    t0 = time.perf_counter()
    fe_stats = asyncio.run(drive())
    wall_s = time.perf_counter() - t0

    ttfts = sorted(r["handle"].ttft_s for r in results
                   if r["handle"] is not None
                   and r["handle"].ttft_s is not None)
    slo_s = args.slo_ms / 1e3
    good_tokens = sum(
        len(r["tokens"]) for r in results
        if r["handle"] is not None
        and r["handle"].state is RequestState.FINISHED
        and r["handle"].ttft_s is not None
        and r["handle"].ttft_s <= slo_s)
    leaked = 0
    if args.kv_layout == "paged":
        engine.pool.pages.check()      # raises PageLeakError on leak
        leaked = engine.pool.pages.num_pages - engine.pool.pages.n_free
    report = {
        "wall_s": wall_s,
        "ttft_p50_ms": (1e3 * float(np.median(ttfts)) if ttfts
                        else float("nan")),
        "ttft_p99_ms": (1e3 * float(np.percentile(ttfts, 99)) if ttfts
                        else float("nan")),
        "slo_ms": args.slo_ms,
        "slo_attainment": (len([t for t in ttfts if t <= slo_s])
                           / max(len(ttfts), 1)),
        "goodput_tok_per_s": good_tokens / wall_s,
        "tok_per_s": fe_stats["tokens"] / wall_s,
        "shed": int(fe_stats["frontend_shed"]),
        "cancelled": int(fe_stats["cancelled"]),
        "expired": int(fe_stats["expired"]),
        "finished": int(fe_stats["finished"]),
        "rounds": int(fe_stats["frontend_rounds"]),
        "leaked_pages": int(leaked),
    }
    return engine, wall_s, report


def run_legacy_loop(model, params, prompts, args):
    """Old path: per-request prefill + Python decode loop, sequential."""
    max_len = args.prompt_len + args.new_tokens + 1
    engine = ServeEngine(model, params, max_len=max_len)
    t0 = time.perf_counter()
    waits, tokens = [], 0
    for prompt in prompts:
        waits.append(time.perf_counter() - t0)
        out = engine.generate({"tokens": jnp.asarray(prompt)[None, :]},
                              args.new_tokens)
        tokens += int(out.tokens.shape[0] * out.tokens.shape[1])
    dt = time.perf_counter() - t0
    return tokens, dt, np.asarray(waits)


def enforce_no_failures(engine) -> None:
    """Without a fault plan nothing may fail: a FAILED request then
    means a real error was absorbed, and the run exits non-zero."""
    if engine.fault_plan is not None:
        return
    failed = [r.rid for r in engine.finished
              if r.state is RequestState.FAILED]
    if failed:
        print(f"[serve] FATAL: {len(failed)} requests FAILED without a "
              f"fault plan (rids {failed[:8]})")
        raise SystemExit(1)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--num-layers", type=int, default=None,
                    help="depth cut: serve only the first N layers at "
                         "the config's published widths (random "
                         "weights), for a model whose full depth does "
                         "not fit the device")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--capacity", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--decode-chunk", type=int, default=2)
    ap.add_argument("--kv-layout", default="slots",
                    choices=("slots", "paged"),
                    help="KV arena layout: contiguous [K, max_len] slots "
                         "or the block-table page arena (equal bytes, "
                         "per-slot contexts may exceed max_len)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged layout)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page-arena size (paged layout; default: "
                         "capacity * ceil(max_len / page_size), the "
                         "contiguous arena's byte budget)")
    ap.add_argument("--page-growth", default="lazy",
                    choices=("lazy", "eager"),
                    help="paged layout: grant pages lazily per decode "
                         "chunk (admission gated by a headroom "
                         "watermark) or reserve the worst case at "
                         "insert")
    ap.add_argument("--allocator-wait", default=None,
                    choices=("auto", "spin", "spin_backoff", "sleeping",
                             "adaptive"),
                    help="page-allocator mutex wait strategy; adaptive "
                         "re-selects between rounds from measured "
                         "contention (default: select_impl's choice)")
    ap.add_argument("--prefix-sharing", default="auto",
                    choices=("auto", "on", "off"),
                    help="copy-on-write prompt-prefix sharing on the "
                         "paged arena: requests whose prompt repeats a "
                         "live prefix adopt its pages read-only and "
                         "split on first divergent write (auto = on for "
                         "paged greedy attention serving; DESIGN.md §11)")
    ap.add_argument("--prefix-cache", default="off",
                    choices=("auto", "on", "off"),
                    help="page-granular prefix cache on the paged arena: "
                         "retired requests donate their written full "
                         "pages to an LRU trie instead of freeing them, "
                         "so later prompts (and multi-turn follow-ups) "
                         "re-adopt them without re-prefilling (auto = on "
                         "for paged greedy chunked-prefill serving; "
                         "DESIGN.md §14)")
    ap.add_argument("--cache-watermark", type=float, default=None,
                    help="free-page fraction below which admission "
                         "evicts LRU cache entries to fund grants "
                         "(default: the lazy-growth admit headroom)")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=None,
                    help="continuous chunked prefill: prefill admitted "
                         "prompts this many tokens per scheduler round "
                         "inside the decode dispatch instead of one "
                         "whole-prompt prefill at admission (greedy "
                         "attention archs only; DESIGN.md §12)")
    ap.add_argument("--attention-impl", default="gather",
                    choices=("gather", "fused"),
                    help="paged decode read path: gather-then-attend "
                         "(the executable reference) or the fused "
                         "one-pass Pallas block-table kernel "
                         "(kernels/paged_attention; interpret tier on "
                         "CPU, compiled on TPU; DESIGN.md §16)")
    ap.add_argument("--bucketed-dispatch", default="auto",
                    choices=("auto", "on", "off"),
                    help="bucketed compiled dispatch: gather active "
                         "slots into power-of-2 occupancy buckets so "
                         "scheduler rounds never retrace as occupancy "
                         "shifts (auto = on for paged greedy attention "
                         "serving; DESIGN.md §16)")
    ap.add_argument("--round-token-budget", type=int, default=None,
                    help="per-round token budget the scheduler fills "
                         "with decode rows first, then prefill chunks "
                         "(default: capacity * (decode_chunk + chunk) — "
                         "every slot funded; smaller budgets throttle "
                         "prefill FIFO-fairly, never decode)")
    ap.add_argument("--open-loop", action="store_true",
                    help="drive production-shaped traffic through the "
                         "asyncio front-end instead of the closed-loop "
                         "batch drive: Poisson arrivals, token "
                         "streaming, mid-flight cancellation, TTFT SLO "
                         "(serve/frontend.py, DESIGN.md §13)")
    ap.add_argument("--arrival-rate", type=float, default=16.0,
                    help="open loop: mean client arrival rate, "
                         "requests/s (exponential inter-arrival gaps)")
    ap.add_argument("--cancel-rate", type=float, default=0.0,
                    help="open loop: fraction of clients that cancel "
                         "mid-generation after a random number of "
                         "streamed tokens")
    ap.add_argument("--slo-ms", type=float, default=1000.0,
                    help="open loop: time-to-first-token SLO; goodput "
                         "counts only finished requests that met it")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="open loop: hard per-request deadline armed in "
                         "the engine — queued requests past it are shed "
                         "as EXPIRED, active ones are deprioritized for "
                         "prefill chunks and evicted first under page "
                         "pressure (default: no deadlines)")
    ap.add_argument("--intake-limit", type=int, default=256,
                    help="open loop: bound on the ungranted population "
                         "(front-end intake + engine FIFO queue); "
                         "submits past it are shed explicitly")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="chaos mode: per-consult probability of each "
                         "injected transient fault (allocator batch "
                         "abort, dispatch exception, stuck lock holder "
                         "— serve/faults.py, DESIGN.md §15); 0 = off. "
                         "Every fault must be recovered: the run still "
                         "finishes all requests and the post-drain "
                         "leak gate still applies")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="FaultPlan seed — same seed + same workload "
                         "injects the same faults at the same points")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--legacy", action="store_true",
                    help="also run the old per-request loop")
    ap.add_argument("--sync-backend", default="auto",
                    choices=("auto", "host", "kernel", "ref"),
                    help="admission-planner backend (auto = pick from "
                         "the machine abstraction)")
    ap.add_argument("--admission-sem", default="auto",
                    choices=("auto", "sleeping", "spin", "spin_backoff"),
                    help="live admission-gate semaphore algorithm "
                         "(auto = paper Table-5 selection)")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()

    cfg, model, params = build(args)
    sync = make_sync_library(args)
    choice = sync.choice(PrimitiveKind.SEMAPHORE,
                         semaphore_initial=args.capacity)
    gate = (choice.algorithm if args.admission_sem == "auto"
            else args.admission_sem)
    planner = get_backend(sync.planning_backend_name())
    print(f"[serve] sync: gate={gate} (selected "
          f"{choice.algorithm}/{choice.strategy.value}) "
          f"planner={planner.name}[{planner.tier}] "
          f"machine={sync.machine.name}({sync.machine_class()})")
    key = jax.random.PRNGKey(args.seed)
    prompts = np.asarray(jax.random.randint(
        key, (args.requests, args.prompt_len), 0, cfg.vocab_size))

    # --- predicted timeline (paper Algorithm 5 as the planning kernel)
    service_est = np.full(args.requests, float(args.new_tokens), np.float32)
    arrivals = np.zeros(args.requests, np.float32)
    plan = plan_admission(arrivals, service_est, args.capacity, lib=sync)
    print(f"[serve] plan[{plan.backend}]: p50 wait {plan.p50_wait:.1f} steps "
          f"p99 {plan.p99_wait:.1f} makespan {plan.makespan:.1f} "
          f"queued {int(plan.waited.sum())}/{args.requests}")

    report = None
    if args.open_loop:
        engine, dt, report = run_open_loop(model, params, prompts, args,
                                           sync=sync)
    else:
        engine, dt = run_slot_engine(model, params, prompts, args,
                                     sync=sync)
    st = engine.stats()
    print(f"[serve] {args.kv_layout} engine: {int(st['finished'])} requests, "
          f"{int(st['tokens'])} tokens in {dt:.2f}s "
          f"({st['tokens'] / dt:,.0f} tok/s), "
          f"{int(st['decode_dispatches'])} dispatches, "
          f"p50 wait {st['p50_wait_steps']:.0f} steps "
          f"p99 {st['p99_wait_steps']:.0f}")
    if engine.prefill_chunk:
        print(f"[serve] chunked prefill: {engine.prefill_chunk} tok/chunk, "
              f"budget {engine.round_token_budget} tok/round, "
              f"{int(st['prefill_chunks'])} chunks over "
              f"{int(st['prefill_tokens'])} prompt tokens, "
              f"pad fraction {st['pad_fraction']:.3f}, "
              f"{int(st['decode_rounds_stalled_by_prefill'])} decode "
              f"rounds stalled by prefill")
    elif args.prefill_chunk_tokens:
        print("[serve] chunked prefill requested but disabled "
              "(needs greedy decoding + attention-only arch); "
              f"one-shot pad fraction {st['pad_fraction']:.3f}")
    if args.kv_layout == "paged":
        pool = engine.pool
        bd = ("on" if engine.bucketed_dispatch else "off")
        disp = (f" ({int(st['dispatch_trace_keys'])} traced shapes, "
                f"{int(st['dispatch_retraces'])} retraces)"
                if engine.bucketed_dispatch else "")
        print(f"[serve] paged attention: impl={engine.attention_impl}, "
              f"attention_kv_grouped {st['attention_kv_grouped']:.1f}, "
              f"arena_in_place {st['arena_in_place']:.1f}, "
              f"bucketed dispatch {bd}{disp}")
        print(f"[serve] page arena: {pool.pages.num_pages} pages x "
              f"{pool.page_size} tokens, peak "
              f"{int(st['pages_peak_in_use'])} in use, "
              f"{int(st['page_allocs'])} allocs / "
              f"{int(st['page_frees'])} frees under "
              f"{type(pool.pages.mutex).__name__}"
              f"[{pool.pages.wait_strategy.value}], "
              f"virtual max_len {pool.virtual_max_len} "
              f"(slot arena row: {engine.max_len})")
        print(f"[serve] allocator lock ({engine.page_growth} growth): "
              f"{int(st['lock_acquires'])} acquires "
              f"({int(st['lock_contended_acquires'])} contended, "
              f"{st['lock_held_s'] * 1e3:.2f}ms held), "
              f"{st['lock_acquires_per_token']:.4f} per token vs "
              f"{st['per_page_lock_acquires_per_token']:.4f} one-per-page; "
              f"{int(st['page_pauses'])} pauses, "
              f"{int(st['page_preemptions'])} preemptions, "
              f"{int(st['lock_retunes'])} retunes")
        share = "on" if engine.prefix_sharing else "off"
        print(f"[serve] prefix sharing {share}: "
              f"{int(st['prefix_hits'])} hits, "
              f"{int(st['shared_pages_adopted'])} pages adopted, "
              f"{int(st['cow_splits'])} CoW splits, "
              f"{st['pages_per_token']:.3f} pages alloc'd per token")
        if engine.prefix_cache is not None:
            print(f"[serve] prefix cache: "
                  f"{int(st['cache_hits'])} hits "
                  f"(rate {st['cache_hit_rate']:.2f}), "
                  f"{int(st['cache_tokens_served'])} tokens served, "
                  f"{int(st['prefill_tokens_saved'])} prefill tokens "
                  f"saved; {int(st['cache_pages_held'])} pages held / "
                  f"{int(st['cache_pages_donated'])} donated / "
                  f"{int(st['cache_pages_evicted'])} evicted")
        elif args.prefix_cache != "off":
            print("[serve] prefix cache requested but disabled "
                  "(needs paged layout + greedy chunked prefill)")
    if engine.fault_plan is not None:
        fp = engine.fault_plan
        print(f"[serve] chaos (seed {fp.seed}, rate {args.fault_rate}): "
              f"{int(st['faults_injected'])} faults injected "
              f"{dict(fp.by_kind)}, "
              f"{int(st['rounds_retried'])} rounds retried, "
              f"{int(st['requests_quarantined'])} quarantined, "
              f"{int(st['failed'])} failed, "
              f"{int(st.get('watchdog_trips', 0))} watchdog trips")
    fifo_ok = engine.grant_log == sorted(engine.grant_log)
    print(f"[serve] FIFO grant order: {'OK' if fifo_ok else 'VIOLATED'} "
          f"({len(engine.grant_log)} grants, semaphore in-flight "
          f"{engine.admission.in_flight})")
    if report is not None:
        print(f"[serve] open loop: {report['finished']} finished / "
              f"{report['cancelled']} cancelled / "
              f"{report['expired']} expired / {report['shed']} shed "
              f"over {report['rounds']} rounds in {report['wall_s']:.2f}s")
        print(f"[serve] open loop: TTFT p50 {report['ttft_p50_ms']:.0f}ms "
              f"p99 {report['ttft_p99_ms']:.0f}ms, SLO {args.slo_ms:.0f}ms "
              f"met by {report['slo_attainment']:.0%}, goodput "
              f"{report['goodput_tok_per_s']:,.0f} tok/s of "
              f"{report['tok_per_s']:,.0f} total; "
              f"time-in-state p99 (steps): queued "
              f"{st['p99_queued_steps']:.0f} / prefill "
              f"{st['p99_prefill_steps']:.0f} / decode "
              f"{st['p99_decode_steps']:.0f}")
        if args.kv_layout == "paged":
            print(f"[serve] open loop: leaked pages after drain: "
                  f"{report['leaked_pages']} (free-list "
                  f"{engine.pool.pages.n_free}/"
                  f"{engine.pool.pages.num_pages})")

    enforce_leak_gate(engine)
    enforce_no_failures(engine)

    if args.legacy:
        tokens, dt_old, waits = run_legacy_loop(model, params, prompts, args)
        print(f"[serve] legacy loop: {tokens} tokens in {dt_old:.2f}s "
              f"({tokens / dt_old:,.0f} tok/s), "
              f"p50 wait {np.median(waits):.2f}s "
              f"p99 {np.percentile(waits, 99):.2f}s")
        print(f"[serve] slot-engine speedup: {dt_old / dt:.2f}x")
    return engine


if __name__ == "__main__":
    main()
