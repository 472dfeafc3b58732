"""Serving benchmark: legacy per-request loop vs slot-pool batching.

Measures tokens/s and queue-wait percentiles (p50/p99) under Poisson
arrivals at several concurrency budgets K, for

  * ``legacy``  — the old per-request Python decode loop (sequential),
  * ``slots``   — the semaphore-gated continuous-batching slot engine,
  * ``paged``   — the same engine on the block-table page arena
    (serve/kv_pages.py): equal arena bytes, mutex-gated page
    allocator on the admission/retire hot path. Paged rows run lazy
    growth by default and ALWAYS measure the eager (PR 3 worst-case
    reservation) baseline alongside on the same trace: token streams
    must match, and the row reports the allocator lock ledger —
    ``lock_acquires_per_token`` plus its drop vs the eager run's
    one-acquire-per-page accounting (``lock_drop_vs_pr3_per_page``,
    the tentpole acceptance number),

plus the Algorithm-5 kernel-planned wait percentiles for the same trace,
so the predicted and measured timelines can be compared. ``--kv-layout``
selects which engine rows to measure (CI runs both).

A dedicated **shared-prefix trace** (``paged_prefix`` rows) measures
copy-on-write prefix sharing (DESIGN.md §11): groups of requests repeat
a live prompt with arrivals staggered one round apart, and the paged
engine runs with ``--prefix-sharing on`` and ``off`` on the identical
trace. Token streams must match bit-for-bit; the ``on`` row must
allocate strictly fewer physical pages (``pages_per_token`` — prefix
pages become increfs) at no increase in ``lock_acquires_per_token``
(refcount traffic rides the existing batched critical sections). CI
asserts both deltas.

A dedicated **interleaved-arrivals trace** (``interleaved`` rows)
measures continuous chunked prefill (DESIGN.md §12): long prompts and
short decodes arrive interleaved on a page-tight arena, and the paged
engine runs the identical trace with ``prefill_chunk_tokens`` set
(chunked) and unset (one-shot). Token streams must match bit-for-bit;
chunked admission — bookkeeping plus the first chunk's page, instead of
a whole padded bucket — must cut the p99 queue wait, at no increase in
``lock_acquires_per_token`` (chunk page demand folds into the existing
per-round top-up batch) and a strictly lower prefill pad fraction. CI
asserts all four deltas.

A **multi-turn prefix-cache trace** (``BENCH_prefix_cache.json``)
replays a seeded conversation workload — shared system prompts plus
follow-up turns embedding the parent's actual reply, generated by the
reusable fuzz-trace machinery in serve/fuzz.py — with the page-granular
prefix cache (DESIGN.md §14) on and off. Streams must match
bit-for-bit; the on row must land cache hits (``cache_hit_rate > 0``),
skip prefill chunks (``prefill_tokens_saved > 0``), leak zero pages
once the cache is dropped, and hold allocator lock acquires per token
at or below the off run's. A batch of pool-level
lifecycle fuzz traces runs alongside and reports its pass count.

An **open-loop front-end trace** (``BENCH_frontend.json``) drives the
same engine through the asyncio front-end (serve/frontend.py, DESIGN.md
§13): concurrent clients arrive Poisson on the wall clock, stream
tokens as rounds complete, and every ``--cancel-every``-th client hangs
up mid-generation. The trace reports p50/p99 time-to-first-token,
goodput under the ``--slo-ms`` TTFT SLO, and the cancellation-safety
ledger CI gates on: zero leaked pages after the drain (refcount-safe
with shared prefixes) and survivors' token streams bit-identical to the
closed-loop driver on the same prompts.

  PYTHONPATH=src python benchmarks/servebench.py --smoke

``--smoke`` runs a reduced sweep and writes ``BENCH_serve.json`` so CI
records the perf trajectory.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def poisson_arrival_steps(n: int, capacity: int, new_tokens: int,
                          load: float, rng) -> np.ndarray:
    """Arrival step-times for offered load ``load`` (fraction of replica
    token throughput: rate = load * K / service_steps)."""
    rate = load * capacity / float(new_tokens)
    gaps = rng.exponential(1.0 / rate, size=n)
    return np.floor(np.cumsum(gaps)).astype(np.int64)


def shared_prefix_prompts(n: int, prompt_len: int, n_groups: int,
                          vocab: int, rng) -> np.ndarray:
    """The prefix-sharing arrival trace's prompts: ``n_groups`` distinct
    random prompts, each repeated round-robin — every follower's prompt
    is a full-length (page-aligned by construction when prompt_len is a
    page multiple) repeat of a live leader's, the workload shape of
    shared system preambles / few-shot headers."""
    base = rng.integers(0, vocab, (n_groups, prompt_len)).astype(np.int32)
    return base[np.arange(n) % n_groups]


def staggered_arrivals(n: int, n_groups: int, decode_chunk: int
                       ) -> np.ndarray:
    """Round-robin waves: one request per group per scheduler round
    (``decode_chunk`` steps). Same-round admissions cannot adopt from
    each other (the donor's pages exist only after its insert), so the
    wave spacing guarantees every follower's admission finds the
    previous member of its group still decoding — a live donor — as
    long as ``n_groups`` leaves slot headroom (the trace runner keeps
    ``n_groups <= capacity / 2``)."""
    return (decode_chunk * (np.arange(n) // n_groups)).astype(np.int64)


def bench_slot_engine(model, params, prompts, arrivals, *, capacity,
                      new_tokens, decode_chunk, seed, kv_layout="slots",
                      page_size=16, page_growth="lazy",
                      allocator_wait=None, prefix_sharing="auto",
                      prefill_chunk_tokens=None, round_token_budget=None,
                      num_pages=None, attention_impl="gather",
                      bucketed_dispatch="auto"):
    from repro.serve.engine import SlotServeEngine
    # ``prompts`` may be a rectangular [n, L] array or a list of 1-D
    # arrays of different lengths (the interleaved trace mixes long
    # prompts with short ones)
    n = len(prompts)
    prompt_len = max(int(np.asarray(p).size) for p in prompts)
    max_len = prompt_len + new_tokens + 1
    engine = SlotServeEngine(model, params, capacity=capacity,
                             max_len=max_len, decode_chunk=decode_chunk,
                             seed=seed, kv_layout=kv_layout,
                             page_size=page_size, page_growth=page_growth,
                             allocator_wait=allocator_wait,
                             prefix_sharing=prefix_sharing,
                             prefill_chunk_tokens=prefill_chunk_tokens,
                             round_token_budget=round_token_budget,
                             num_pages=num_pages,
                             attention_impl=attention_impl,
                             bucketed_dispatch=bucketed_dispatch)
    # warm the prefill/decode traces outside the timed region (the
    # longest prompt compiles both chunked-round traces: chunk=C while
    # prefilling, chunk=0 for its pure-decode tail), then reset every
    # counter the report reads (step clock included, so the arrival
    # schedule starts at 0)
    warm = max(prompts, key=lambda p: np.asarray(p).size)
    engine.submit(warm, max_new_tokens=min(2, new_tokens))
    engine.run_until_done()
    engine.finished.clear()
    engine.grant_log.clear()
    engine.decode_dispatches = 0
    engine.step_clock = 0
    engine.pauses = engine.preemptions = 0
    engine.prefix_hits = engine.shared_pages_adopted = 0
    engine.cow_splits = 0
    engine.prefill_tokens = engine.pad_tokens = 0
    engine.prefill_chunks = 0
    engine.decode_rounds_stalled_by_prefill = 0
    engine.admission.admitted = engine.admission.completed = 0
    if kv_layout == "paged":
        engine.pool.pages.reset_stats()

    t0 = time.perf_counter()
    nxt = 0
    while nxt < n or engine.queue or engine.active:
        while nxt < n and arrivals[nxt] <= engine.step_clock:
            engine.submit(prompts[nxt], new_tokens)
            nxt += 1
        if engine.step() == 0 and not engine.queue and nxt < n:
            engine.step_clock += 1          # idle tick until next arrival
    dt = time.perf_counter() - t0
    st = engine.stats()
    fifo_ok = engine.grant_log == sorted(engine.grant_log)
    row = {
        "tokens": int(st["tokens"]),
        "wall_s": dt,
        "tok_per_s": st["tokens"] / dt,
        "p50_wait_steps": st["p50_wait_steps"],
        "p99_wait_steps": st["p99_wait_steps"],
        "p50_wait_s": st["p50_wait_s"],
        "p99_wait_s": st["p99_wait_s"],
        "decode_dispatches": int(st["decode_dispatches"]),
        "fifo_ok": bool(fifo_ok),
        # chunked-prefill ledger (one-shot rows report it too: their
        # pad tokens are the bucket padding chunking exists to shed)
        "prefill_chunk_tokens": int(st["prefill_chunk_tokens"]),
        "prefill_tokens": int(st["prefill_tokens"]),
        "pad_tokens": int(st["pad_tokens"]),
        "pad_fraction": float(st["pad_fraction"]),
        "prefill_chunks": int(st["prefill_chunks"]),
        "decode_rounds_stalled_by_prefill": int(
            st["decode_rounds_stalled_by_prefill"]),
        # paged-attention read path + bucketed-dispatch ledger (§16)
        "attention_impl": engine.attention_impl,
        "bucketed_dispatch": bool(engine.bucketed_dispatch),
        "dispatch_traces": int(st["dispatch_traces"]),
        "dispatch_trace_keys": int(st["dispatch_trace_keys"]),
        "dispatch_retraces": int(st["dispatch_retraces"]),
    }
    streams = {r.rid: list(r.out_tokens) for r in engine.finished}
    if kv_layout == "paged":
        engine.pool.check()                  # leak-free after the drain
        row.update({
            "page_size": page_size,
            "page_growth": engine.page_growth,
            "allocator_wait": engine.pool.pages.wait_mode,
            "wait_strategy": engine.pool.pages.wait_strategy.value,
            "pages_total": int(st["pages_total"]),
            "pages_peak_in_use": int(st["pages_peak_in_use"]),
            "page_allocs": int(st["page_allocs"]),
            "page_frees": int(st["page_frees"]),
            "page_pauses": int(st["page_pauses"]),
            "page_preemptions": int(st["page_preemptions"]),
            "lock_acquires": int(st["lock_acquires"]),
            "lock_contended_acquires": int(st["lock_contended_acquires"]),
            "lock_held_s": float(st["lock_held_s"]),
            "lock_acquires_per_token": float(st["lock_acquires_per_token"]),
            # the PR 3 "per-page" accounting the acceptance criterion
            # benchmarks against: one lock acquisition per page moved
            "per_page_lock_acquires_per_token": float(
                st["per_page_lock_acquires_per_token"]),
            # prefix sharing's ledger (DESIGN.md §11)
            "prefix_sharing": bool(engine.prefix_sharing),
            "pages_alloced": int(st["pages_alloced"]),
            "pages_per_token": float(st["pages_per_token"]),
            "prefix_hits": int(st["prefix_hits"]),
            "shared_pages_adopted": int(st["shared_pages_adopted"]),
            "cow_splits": int(st["cow_splits"]),
        })
    return row, streams


def bench_open_loop(model, params, prompts, closed_streams, *, capacity,
                    new_tokens, decode_chunk, seed, page_size,
                    prefix_sharing, prefill_chunk_tokens, cancel_every,
                    cancel_after_tokens, arrival_rate, slo_ms,
                    intake_limit=256):
    """Open-loop trace through the asyncio front-end: Poisson wall-clock
    arrivals, token streaming, every ``cancel_every``-th client hanging
    up after ``cancel_after_tokens`` streamed tokens.

    ``closed_streams`` are the closed-loop driver's per-prompt greedy
    streams on the identical engine config; survivors must match them
    bit-for-bit (greedy streams depend only on the prompt, so neither
    arrival timing nor other clients' cancellations may show through).
    """
    from repro.serve.engine import RequestState, SlotServeEngine
    from repro.serve.frontend import AsyncFrontend, IntakeFullError

    n = len(prompts)
    prompt_len = max(int(np.asarray(p).size) for p in prompts)
    max_len = prompt_len + new_tokens + 1
    engine = SlotServeEngine(model, params, capacity=capacity,
                             max_len=max_len, decode_chunk=decode_chunk,
                             seed=seed, kv_layout="paged",
                             page_size=page_size,
                             prefix_sharing=prefix_sharing,
                             prefill_chunk_tokens=prefill_chunk_tokens)
    # warm the compiled traces so TTFT measures scheduling, not jit
    warm = max(prompts, key=lambda p: np.asarray(p).size)
    engine.submit(warm, max_new_tokens=min(2, new_tokens))
    engine.run_until_done()
    engine.finished.clear()
    engine.grant_log.clear()
    engine.decode_dispatches = 0
    engine.step_clock = 0
    engine.cancellations = engine.expiries = 0
    engine.pool.pages.reset_stats()

    rng = np.random.default_rng(seed + 3)
    gaps_s = rng.exponential(1.0 / arrival_rate, n)
    cancels = {i for i in range(n)
               if cancel_every and i % cancel_every == cancel_every - 1}
    records = []

    async def client(fe, i, prompt):
        rec = {"i": i, "tokens": [], "handle": None, "shed": False}
        records.append(rec)
        try:
            h = await fe.submit(prompt, new_tokens)
        except IntakeFullError:
            rec["shed"] = True
            return
        rec["handle"] = h
        async for tok in h:
            rec["tokens"].append(tok)

    # hang up once the client has its tokens-in-hand quota. Driving the
    # cancel from the between-rounds hook (rather than the consumer
    # coroutine) makes it deterministic: generations run >= 4 rounds and
    # the quota is reached by round 1-2, so every cancel lands while its
    # request is still mid-flight — what the leak gate must exercise.
    async def hook(fe):
        for rec in records:
            h = rec["handle"]
            if (h is not None and rec["i"] in cancels
                    and h._streamed >= cancel_after_tokens
                    and not h._cancel_requested):
                h.cancel()

    async def drive():
        async with AsyncFrontend(engine, intake_limit=intake_limit,
                                 round_hook=hook) as fe:
            tasks = []
            for i, prompt in enumerate(prompts):
                await asyncio.sleep(gaps_s[i])
                tasks.append(asyncio.ensure_future(client(fe, i, prompt)))
            await asyncio.gather(*tasks)
            await fe.drain()
            return fe

    t0 = time.perf_counter()
    fe = asyncio.run(drive())
    wall_s = time.perf_counter() - t0

    # cancellation safety: the drained arena must be exactly full again
    engine.pool.pages.check()
    leaked = engine.pool.pages.num_pages - engine.pool.pages.n_free

    # survivors = clients that never asked to cancel (a cancelling
    # client that lost the race to natural completion stops consuming
    # its stream, so its local token list is truncated by design)
    survivors_match = all(
        rec["tokens"] == closed_streams[rec["i"]]
        for rec in records
        if rec["i"] not in cancels
        and rec["handle"] is not None
        and rec["handle"].state is RequestState.FINISHED)
    ttfts = sorted(r["handle"].ttft_s for r in records
                   if r["handle"] is not None
                   and r["handle"].ttft_s is not None)
    slo_s = slo_ms / 1e3
    good_tokens = sum(
        len(r["tokens"]) for r in records
        if r["handle"] is not None
        and r["handle"].state is RequestState.FINISHED
        and r["handle"].ttft_s is not None
        and r["handle"].ttft_s <= slo_s)
    st = fe.stats()
    return {
        "requests": n,
        "capacity": capacity,
        "arrival_rate": arrival_rate,
        "cancel_every": cancel_every,
        "wall_s": wall_s,
        "rounds": int(st["frontend_rounds"]),
        "finished": int(st["finished"]),
        "cancelled": int(st["cancelled"]),
        "expired": int(st["expired"]),
        "shed": int(st["frontend_shed"]),
        "tokens": int(st["tokens"]),
        "tok_per_s": st["tokens"] / wall_s,
        "goodput_tok_per_s": good_tokens / wall_s,
        "slo_ms": slo_ms,
        "slo_attainment": (len([t for t in ttfts if t <= slo_s])
                           / max(len(ttfts), 1)),
        "ttft_p50_ms": (1e3 * float(np.median(ttfts)) if ttfts
                        else float("nan")),
        "ttft_p99_ms": (1e3 * float(np.percentile(ttfts, 99)) if ttfts
                        else float("nan")),
        "p99_queued_steps": float(st["p99_queued_steps"]),
        "p99_prefill_steps": float(st["p99_prefill_steps"]),
        "p99_decode_steps": float(st["p99_decode_steps"]),
        "prefix_hits": int(st["prefix_hits"]),
        "leaked_pages": int(leaked),
        "survivor_streams_match_closed_loop": bool(survivors_match),
        "fifo_ok": bool(engine.grant_log == sorted(engine.grant_log)),
    }


def bench_prefix_cache(model, params, *, capacity, decode_chunk, seed,
                       page_size, vocab, requests, fuzz_traces=0):
    """Multi-turn conversation trace, prefix cache on vs off
    (DESIGN.md §14): shared system prompts plus follow-up turns whose
    prompts embed the parent's actual reply, generated and driven by
    the reusable fuzz-trace machinery (serve/fuzz.py). The identical
    trace runs with ``prefix_cache="on"`` and ``"off"``; CI gates on
    the on-row's ledger: cache_hit_rate > 0 and prefill_tokens_saved
    > 0 (retirement donations got re-adopted), token streams
    bit-identical to the off run, zero leaked pages once the cache is
    dropped, and allocator lock acquires per token not above the off
    run's (donation/adoption/eviction all ride existing critical
    sections; per-token, not per-round, because the cache finishes the
    identical trace in fewer rounds). ``fuzz_traces`` pool-level fuzz
    traces (PoolFuzzHarness) run alongside and report their pass count.
    """
    from repro.serve.engine import SlotServeEngine
    from repro.serve.fuzz import PoolFuzzHarness, drive_trace, gen_trace

    events = gen_trace(seed + 5, n_requests=requests, vocab=vocab,
                       max_prompt=12, max_new=6, n_system_prompts=2,
                       p_shared=0.85, p_multi_turn=0.5, p_cancel=0.0,
                       arrival_spread=4)
    results, rows = {}, {}
    for mode in ("on", "off"):
        engine = SlotServeEngine(model, params, capacity=capacity,
                                 max_len=160, decode_chunk=decode_chunk,
                                 seed=seed, kv_layout="paged",
                                 page_size=page_size, prefix_sharing="on",
                                 prefix_cache=mode,
                                 prefill_chunk_tokens=page_size)
        # warm the compiled traces, then zero every counter the gates
        # read — including the cache's own ledger (the warm request's
        # retirement donates)
        warm = (np.arange(12, dtype=np.int32) % (vocab - 1)) + 1
        engine.submit(warm, max_new_tokens=2)
        engine.run_until_done()
        engine.finished.clear()
        engine.grant_log.clear()
        engine.decode_dispatches = 0
        engine.step_clock = 0
        engine.prefix_hits = engine.shared_pages_adopted = 0
        engine.cow_splits = 0
        engine.cache_hits = engine.cache_tokens_served = 0
        engine.prefill_tokens_saved = 0
        if engine.prefix_cache is not None:
            engine.drop_prefix_cache()
            pc = engine.prefix_cache
            pc.hits = pc.misses = 0
            pc.pages_donated = pc.pages_duplicate = 0
            pc.pages_evicted = pc.pages_adopted = 0
        engine.pool.pages.reset_stats()

        t0 = time.perf_counter()
        trace_stats = {}
        results[mode] = drive_trace(engine, events,
                                    stats_out=trace_stats)
        dt = time.perf_counter() - t0
        st = engine.stats()
        rounds = max(trace_stats.get("rounds", 1), 1)
        # snapshot the lock ledger BEFORE dropping the cache (the
        # drop's free_batch takes the allocator lock too)
        acquires = engine.pool.pages.lock_stats()["acquires"]
        engine.pool.check()
        if engine.prefix_cache is not None:
            engine.drop_prefix_cache()
        row = {
            "tokens": int(st["tokens"]),
            "wall_s": dt,
            "tok_per_s": st["tokens"] / dt,
            "rounds": rounds,
            "lock_acquires": int(acquires),
            "lock_acquires_per_round": acquires / rounds,
            "lock_acquires_per_token": acquires / max(st["tokens"], 1),
            "prefix_hits": int(st["prefix_hits"]),
            "cache_hits": int(st["cache_hits"]),
            "cache_hit_rate": float(st["cache_hit_rate"]),
            "cache_tokens_served": int(st["cache_tokens_served"]),
            "prefill_tokens_saved": int(st["prefill_tokens_saved"]),
            "leaked_pages": int(engine.pool.pages.in_use),
            "fifo_ok": bool(engine.grant_log == sorted(engine.grant_log)),
        }
        if mode == "on":
            row.update({
                "cache_lookup_hits": int(st["cache_lookup_hits"]),
                "cache_pages_donated": int(st["cache_pages_donated"]),
                "cache_pages_adopted": int(st["cache_pages_adopted"]),
                "cache_pages_evicted": int(st["cache_pages_evicted"]),
            })
        rows[mode] = row

    on, off = rows["on"], rows["off"]
    on["tokens_match_off"] = bool(
        set(results["on"]) == set(results["off"]) and all(
            np.array_equal(results["on"][r]["prompt"],
                           results["off"][r]["prompt"])
            and results["on"][r]["out"] == results["off"][r]["out"]
            for r in results["on"]))
    # the ledger gate: identical token streams, so per-token acquires is
    # the like-for-like ratio — per-ROUND would penalize the cache for
    # finishing the trace in fewer rounds at the same total acquires
    on["lock_ratio_vs_off"] = (
        on["lock_acquires_per_token"] / off["lock_acquires_per_token"]
        if off["lock_acquires_per_token"] else float("inf"))
    on["lock_per_round_ratio_vs_off"] = (
        on["lock_acquires_per_round"] / off["lock_acquires_per_round"]
        if off["lock_acquires_per_round"] else float("inf"))

    passed = 0
    for fseed in range(fuzz_traces // 2):
        for cache in (True, False):
            PoolFuzzHarness(fseed, num_pages=48, page_size=page_size,
                            cache=cache).run(rounds=30)
            passed += 1
    on["fuzz_traces_passed"] = passed
    return {"capacity": capacity, "requests": requests,
            "page_size": page_size, **rows}


def bench_chaos(model, params, *, capacity, decode_chunk, seed, page_size,
                vocab, requests, fault_seed, alloc_rate, dispatch_rate,
                stuck_rate, max_faults):
    """Chaos trace (DESIGN.md §15): the identical seeded conversation
    workload runs fault-free, under transient injected faults (allocator
    batch aborts mid-critical-section, dispatch exceptions, stuck lock
    holders), and with one request poisoned to fail deterministically.

    CI gates on the rows this emits (``BENCH_chaos.json``):

      * faults actually exercised — all three transient kinds fired;
      * every fault recovered or quarantined: the transient run finishes
        every request, the poison run fails exactly one;
      * zero leaked pages after every drain (transactional batches +
        compensating eviction replay);
      * survivor token streams bit-identical to the fault-free run;
      * availability = finished / (finished + quarantined) reported;
      * per-token lock acquires within 1.05x of the fault-free baseline
        (recovery re-acquires only to roll forward aborted batches —
        steady state adds zero).
    """
    from repro.serve.engine import RequestState, SlotServeEngine
    from repro.serve.faults import FaultPlan
    from repro.serve.fuzz import drive_trace, gen_trace

    def run(fault_plan):
        events = gen_trace(seed + 9, n_requests=requests, vocab=vocab,
                           max_prompt=12, max_new=10, n_system_prompts=2,
                           p_shared=0.7, p_multi_turn=0.4, p_cancel=0.0,
                           arrival_spread=4)
        engine = SlotServeEngine(
            model, params, capacity=capacity, max_len=160,
            decode_chunk=decode_chunk, seed=seed, kv_layout="paged",
            page_size=page_size, prefix_sharing="on", prefix_cache="on",
            prefill_chunk_tokens=page_size, fault_plan=fault_plan,
            quarantine_after=3, retry_backoff_s=0.0,
            allocator_watchdog_s=3e-3)
        t0 = time.perf_counter()
        streams = drive_trace(engine, events)
        dt = time.perf_counter() - t0
        st = engine.stats()
        acquires = engine.pool.pages.lock_stats()["acquires"]
        engine.drop_prefix_cache()
        engine.pool.check()
        finished = int(st["finished"])
        quarantined = int(st["requests_quarantined"])
        row = {
            "tokens": int(st["tokens"]),
            "wall_s": dt,
            "tok_per_s": st["tokens"] / dt,
            "finished": finished,
            "failed": int(st["failed"]),
            "faults_injected": int(st["faults_injected"]),
            "faults_by_kind": (dict(fault_plan.by_kind)
                               if fault_plan is not None else {}),
            "rounds_retried": int(st["rounds_retried"]),
            "requests_quarantined": quarantined,
            "watchdog_trips": int(st["watchdog_trips"]),
            "aborted_batches": int(st["aborted_batches"]),
            "availability": finished / max(finished + quarantined, 1),
            "lock_acquires": int(acquires),
            "lock_acquires_per_token": acquires / max(st["tokens"], 1),
            "leaked_pages": int(engine.pool.pages.in_use),
        }
        return row, streams, engine

    def survivors_match(base_streams, streams):
        """Bit-identity over every request both runs served to
        completion from the same resolved prompt."""
        matched = 0
        for rid, a in base_streams.items():
            b = streams.get(rid)
            if b is None or a["cancelled"] or b["cancelled"]:
                continue
            if not np.array_equal(a["prompt"], b["prompt"]):
                continue
            if a["out"] != b["out"]:
                return matched, False
            matched += 1
        return matched, True

    base_row, base_streams, _ = run(None)

    # high per-consult rates so every kind reliably fires, bounded per
    # kind: allocator aborts are the only kind whose recovery re-takes
    # the lock (abort + compensating eviction replay), so they get the
    # tightest cap — that is what keeps the lock-ledger ratio gate at
    # 1.05x meaningful rather than fault-budget-dependent
    chaos_plan = FaultPlan(fault_seed, alloc_rate=alloc_rate,
                           dispatch_rate=dispatch_rate,
                           stuck_rate=stuck_rate, stuck_hold_s=1e-2,
                           max_faults=max_faults,
                           max_per_kind={"alloc": 1, "dispatch": 2,
                                         "stuck": 2})
    chaos_row, chaos_streams, _ = run(chaos_plan)
    n, ok = survivors_match(base_streams, chaos_streams)
    chaos_row["survivors_compared"] = n
    chaos_row["survivor_streams_match_baseline"] = ok
    chaos_row["lock_ratio_vs_baseline"] = (
        chaos_row["lock_acquires_per_token"]
        / base_row["lock_acquires_per_token"]
        if base_row["lock_acquires_per_token"] else float("inf"))

    # poison a mid-trace request: it must be quarantined alone
    poison_rid = 1 + requests // 2
    poison_plan = FaultPlan(fault_seed, poison_rid=poison_rid)
    poison_row, poison_streams, eng = run(poison_plan)
    n, ok = survivors_match(base_streams, poison_streams)
    poison_row["survivors_compared"] = n
    poison_row["survivor_streams_match_baseline"] = ok
    poison_row["poison_rid"] = poison_rid
    failed = [r for r in eng.finished if r.state is RequestState.FAILED]
    poison_row["failed_rids"] = [r.rid for r in failed]
    poison_row["failed_error"] = failed[0].error if failed else None

    return {"capacity": capacity, "requests": requests,
            "fault_seed": fault_seed, "alloc_rate": alloc_rate,
            "dispatch_rate": dispatch_rate, "stuck_rate": stuck_rate,
            "baseline": base_row, "chaos": chaos_row,
            "poison": poison_row}


def bench_legacy(model, params, prompts, *, new_tokens):
    from repro.serve.engine import ServeEngine
    n, prompt_len = prompts.shape
    max_len = prompt_len + new_tokens + 1
    engine = ServeEngine(model, params, max_len=max_len)
    engine.generate({"tokens": jnp.asarray(prompts[0])[None, :]}, 2)  # warm

    t0 = time.perf_counter()
    waits, tokens = [], 0
    for i in range(n):
        waits.append(time.perf_counter() - t0)   # all arrive at t=0
        out = engine.generate(
            {"tokens": jnp.asarray(prompts[i])[None, :]}, new_tokens)
        tokens += int(out.tokens.size)
    dt = time.perf_counter() - t0
    return {
        "tokens": tokens,
        "wall_s": dt,
        "tok_per_s": tokens / dt,
        "p50_wait_s": float(np.median(waits)),
        "p99_wait_s": float(np.percentile(waits, 99)),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--capacities", type=int, nargs="+",
                    default=[1, 4, 8])
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--decode-chunk", type=int, default=4)
    ap.add_argument("--kv-layout", default="both",
                    choices=("slots", "paged", "both"),
                    help="which KV arena layout(s) to measure")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--page-growth", default="lazy",
                    choices=("lazy", "eager"),
                    help="paged-layout reservation policy for the main "
                         "paged rows (the eager baseline is always "
                         "measured alongside for the lock-traffic drop)")
    ap.add_argument("--allocator-wait", default=None,
                    choices=("auto", "spin", "spin_backoff",
                             "sleeping", "adaptive"),
                    help="pin the page allocator's wait strategy "
                         "(default: select_impl's choice)")
    ap.add_argument("--prefix-sharing", default="both",
                    choices=("on", "off", "both"),
                    help="which sharing modes the dedicated "
                         "shared-prefix trace measures (paged layout "
                         "only; 'both' adds the on-vs-off deltas the CI "
                         "gate asserts)")
    ap.add_argument("--prefix-groups", type=int, default=4,
                    help="distinct prompts in the shared-prefix trace "
                         "(every other request repeats one of them)")
    ap.add_argument("--chunked-prefill", default="both",
                    choices=("on", "off", "both"),
                    help="which prefill schedules the dedicated "
                         "interleaved-arrivals trace measures (paged "
                         "layout only; 'both' adds the chunked-vs-"
                         "one-shot deltas the CI gate asserts)")
    ap.add_argument("--interleaved-long-len", type=int, default=None,
                    help="long-prompt length for the interleaved trace "
                         "(default 5 pages; shorts are one page)")
    ap.add_argument("--prefix-cache", default="on", choices=("on", "off"),
                    help="run the dedicated multi-turn prefix-cache "
                         "trace (paged layout only; the trace itself "
                         "measures cache on vs off and reports the "
                         "deltas the CI gate asserts; DESIGN.md §14)")
    ap.add_argument("--cache-requests", type=int, default=10,
                    help="requests in the multi-turn cache trace")
    ap.add_argument("--cache-fuzz-traces", type=int, default=200,
                    help="pool-level lifecycle fuzz traces run alongside "
                         "the cache trace (half cache-on, half off)")
    ap.add_argument("--cache-out", default="BENCH_prefix_cache.json",
                    help="where the prefix-cache trace's report lands")
    ap.add_argument("--fused-attention", default="on",
                    choices=("on", "off"),
                    help="run the fused-vs-gather paged-decode trace "
                         "(kernels/paged_attention, DESIGN.md §16): one "
                         "trace through both read paths; token streams "
                         "must match bit-for-bit and the bucketed "
                         "dispatch must never retrace — the CI "
                         "kernel-equivalence gate reads these rows")
    ap.add_argument("--load", type=float, default=0.8)
    ap.add_argument("--open-loop", default="on", choices=("on", "off"),
                    help="run the open-loop front-end trace (Poisson "
                         "wall-clock arrivals + mid-flight "
                         "cancellations through serve/frontend.py)")
    ap.add_argument("--arrival-rate", type=float, default=50.0,
                    help="open-loop trace: mean wall-clock arrival "
                         "rate, requests/s")
    ap.add_argument("--cancel-every", type=int, default=3,
                    help="open-loop trace: every Nth client cancels "
                         "mid-generation (0 = nobody cancels)")
    ap.add_argument("--cancel-after-tokens", type=int, default=2,
                    help="open-loop trace: tokens a cancelling client "
                         "consumes before hanging up")
    ap.add_argument("--slo-ms", type=float, default=30000.0,
                    help="open-loop trace: TTFT SLO for the goodput "
                         "split (generous by default — CPU smoke "
                         "rounds are slow; tighten on hardware)")
    ap.add_argument("--chaos", default="on", choices=("on", "off"),
                    help="run the fault-injection chaos trace "
                         "(baseline / transient faults / poisoned "
                         "request) and emit --chaos-out")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="FaultPlan seed for the chaos trace")
    ap.add_argument("--fault-rate", type=float, default=0.2,
                    help="per-consult probability for each transient "
                         "fault site (allocator abort, dispatch "
                         "exception, stuck holder); per-kind caps "
                         "inside the bench bound the totals")
    ap.add_argument("--chaos-requests", type=int, default=28,
                    help="root requests in the chaos trace (long "
                         "enough that the bounded fault budget "
                         "amortizes under the 1.05x lock gate)")
    ap.add_argument("--chaos-max-faults", type=int, default=6,
                    help="total injected-fault budget for the chaos "
                         "trace (keeps the lock-overhead gate "
                         "meaningful)")
    ap.add_argument("--chaos-out", default="BENCH_chaos.json",
                    help="chaos trace report (the CI chaos gate "
                         "reads this)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--frontend-out", default="BENCH_frontend.json",
                    help="where the open-loop trace's report lands")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.configs import get_arch
    from repro.models import build_model
    from repro.serve.scheduler import plan_admission

    cfg = get_arch(args.arch)
    cfg = cfg.reduced()  # this bench targets the CPU smoke tier
    if args.smoke:
        args.requests = min(args.requests, 16)
        args.capacities = [1, 4]
        # oversubscribe slightly so admission/retire batches fill up and
        # the steady-state (not the ramp/drain tails) dominates the
        # lock-traffic accounting
        args.load = max(args.load, 1.0)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.requests, args.prompt_len)).astype(np.int32)

    legacy = bench_legacy(model, params, prompts,
                          new_tokens=args.new_tokens)
    print(f"legacy_loop,tok_per_s={legacy['tok_per_s']:.1f},"
          f"p50_wait_s={legacy['p50_wait_s']:.2f},"
          f"p99_wait_s={legacy['p99_wait_s']:.2f}")

    layouts = (("slots", "paged") if args.kv_layout == "both"
               else (args.kv_layout,))
    rows = {"arch": cfg.name, "requests": args.requests,
            "prompt_len": args.prompt_len, "new_tokens": args.new_tokens,
            "decode_chunk": args.decode_chunk, "load": args.load,
            "page_size": args.page_size, "legacy": legacy}
    rows.update({layout: {} for layout in layouts})
    if "paged" in layouts:
        rows["paged_eager"] = {}
    for k in args.capacities:
        arrivals = poisson_arrival_steps(
            args.requests, k, args.new_tokens, args.load, rng)
        plan = plan_admission(arrivals.astype(np.float32),
                              np.full(args.requests, float(args.new_tokens),
                                      np.float32), k)
        for layout in layouts:
            got, streams = bench_slot_engine(
                model, params, prompts, arrivals, capacity=k,
                new_tokens=args.new_tokens, decode_chunk=args.decode_chunk,
                seed=args.seed, kv_layout=layout, page_size=args.page_size,
                page_growth=args.page_growth,
                allocator_wait=args.allocator_wait)
            got["plan_p50_wait_steps"] = plan.p50_wait
            got["plan_p99_wait_steps"] = plan.p99_wait
            got["speedup_vs_legacy"] = got["tok_per_s"] / legacy["tok_per_s"]
            extra = ""
            if layout == "paged":
                # the eager (PR 3 reservation) baseline on the same
                # trace: token streams must match and the lock-traffic
                # drop is the tentpole's acceptance number; when the
                # main rows are already pinned eager, reuse them
                # instead of re-running the identical configuration
                if args.page_growth == "eager":
                    eag, eag_streams = dict(got), streams
                else:
                    eag, eag_streams = bench_slot_engine(
                        model, params, prompts, arrivals, capacity=k,
                        new_tokens=args.new_tokens,
                        decode_chunk=args.decode_chunk, seed=args.seed,
                        kv_layout="paged", page_size=args.page_size,
                        page_growth="eager",
                        allocator_wait=args.allocator_wait)
                eag["plan_p50_wait_steps"] = plan.p50_wait
                eag["plan_p99_wait_steps"] = plan.p99_wait
                eag["speedup_vs_legacy"] = (eag["tok_per_s"]
                                            / legacy["tok_per_s"])
                rows["paged_eager"][str(k)] = eag
                got["eager_lazy_tokens_match"] = bool(streams == eag_streams)
                lat = got["lock_acquires_per_token"]
                got["lock_drop_vs_eager"] = (
                    eag["lock_acquires_per_token"] / lat if lat else
                    float("inf"))
                # the PR 3 baseline the acceptance criterion names:
                # worst-case reservation at insert, one lock acquisition
                # per page moved — i.e. the eager run's per-page ledger
                got["lock_drop_vs_pr3_per_page"] = (
                    eag["per_page_lock_acquires_per_token"] / lat if lat
                    else float("inf"))
                extra = (f",pages_peak={got['pages_peak_in_use']}"
                         f"/{got['pages_total']},"
                         f"growth={got['page_growth']},"
                         f"lock_per_tok={lat:.4f},"
                         f"drop_vs_eager={got['lock_drop_vs_eager']:.2f}x,"
                         f"drop_vs_pr3_per_page="
                         f"{got['lock_drop_vs_pr3_per_page']:.2f}x,"
                         f"tokens_match={got['eager_lazy_tokens_match']}")
            rows[layout][str(k)] = got
            print(f"{layout}_engine_K{k},tok_per_s={got['tok_per_s']:.1f},"
                  f"p50_wait_steps={got['p50_wait_steps']:.1f},"
                  f"p99_wait_steps={got['p99_wait_steps']:.1f},"
                  f"plan_p50={got['plan_p50_wait_steps']:.1f},"
                  f"plan_p99={got['plan_p99_wait_steps']:.1f},"
                  f"speedup={got['speedup_vs_legacy']:.2f}x,"
                  f"fifo_ok={got['fifo_ok']}{extra}")

    # ---- dedicated shared-prefix trace (prefix sharing on vs off) ----
    # Every follower repeats a live leader's prompt, arrivals staggered
    # one round apart so the prefix index is warm at each admission.
    # Sharing must not change a single token (greedy bit-identity);
    # what it changes is the page ledger: prefix pages become increfs.
    if "paged" in layouts and args.kv_layout != "slots":
        k = max(args.capacities)
        # half the slots serve leaders, half followers: every wave's
        # admission finds the previous member of its group still live
        n_groups = max(1, min(args.prefix_groups, k // 2, args.requests))
        # an unaligned prompt length puts the prompt's tail in a partial
        # page, so every adoption ends in a real CoW split at the
        # follower's first generated token — the trace exercises the
        # whole §11 protocol, not just boundary adoption
        sp_prompt_len = args.prompt_len + (args.prompt_len % args.page_size
                                           == 0)
        # long enough generation that page demand arises *mid-flight*
        # (past the prefill bucket's grant): the off-run then pays grow
        # acquires for pages the on-run never allocates, which is where
        # sharing's lock story shows up — splits fold into grow rounds
        sp_new_tokens = max(3 * args.new_tokens, 2 * args.decode_chunk)
        sp_prompts = shared_prefix_prompts(
            args.requests, sp_prompt_len, n_groups, cfg.vocab_size,
            np.random.default_rng(args.seed + 1))
        sp_arrivals = staggered_arrivals(args.requests, n_groups,
                                         args.decode_chunk)
        modes = (("on", "off") if args.prefix_sharing == "both"
                 else (args.prefix_sharing,))
        sp_rows, sp_streams = {}, {}
        for mode in modes:
            got, streams = bench_slot_engine(
                model, params, sp_prompts, sp_arrivals, capacity=k,
                new_tokens=sp_new_tokens, decode_chunk=args.decode_chunk,
                seed=args.seed, kv_layout="paged",
                page_size=args.page_size, page_growth=args.page_growth,
                allocator_wait=args.allocator_wait, prefix_sharing=mode)
            sp_rows[mode] = got
            sp_streams[mode] = streams
        if len(modes) == 2:
            on, off = sp_rows["on"], sp_rows["off"]
            on["tokens_match_off"] = bool(
                sp_streams["on"] == sp_streams["off"])
            on["pages_drop_vs_off"] = (
                off["pages_per_token"] / on["pages_per_token"]
                if on["pages_per_token"] else float("inf"))
            on["lock_ratio_vs_off"] = (
                on["lock_acquires_per_token"]
                / off["lock_acquires_per_token"]
                if off["lock_acquires_per_token"] else float("inf"))
        rows["paged_prefix"] = {"capacity": k, "groups": n_groups,
                                **sp_rows}
        for mode in modes:
            r = sp_rows[mode]
            extra = ""
            if mode == "on" and "pages_drop_vs_off" in r:
                extra = (f",pages_drop_vs_off={r['pages_drop_vs_off']:.2f}x,"
                         f"lock_ratio_vs_off={r['lock_ratio_vs_off']:.2f},"
                         f"tokens_match={r['tokens_match_off']}")
            print(f"paged_prefix_{mode}_K{k},"
                  f"tok_per_s={r['tok_per_s']:.1f},"
                  f"pages_per_token={r['pages_per_token']:.3f},"
                  f"lock_per_tok={r['lock_acquires_per_token']:.4f},"
                  f"prefix_hits={r['prefix_hits']},"
                  f"shared_pages={r['shared_pages_adopted']},"
                  f"cow_splits={r['cow_splits']}{extra}")

    # ---- fused paged-decode trace (gather vs fused read path, §16) ----
    # The same arrival trace served twice, switching only
    # ``attention_impl``: the gather path is the executable reference,
    # the fused Pallas kernel (interpret tier on CPU) must reproduce
    # its greedy streams token for token; bucketed dispatch runs under
    # both and its ledger must show zero retraces. Chunked prefill is
    # on so both chunk ∈ {0, C} dispatch variants are exercised.
    if args.fused_attention == "on" and "paged" in layouts:
        k = max(args.capacities)
        fa_arrivals = poisson_arrival_steps(
            args.requests, k, args.new_tokens, max(args.load, 1.0),
            np.random.default_rng(args.seed + 5))
        fa_rows, fa_streams = {}, {}
        for impl in ("gather", "fused"):
            got, streams = bench_slot_engine(
                model, params, prompts, fa_arrivals, capacity=k,
                new_tokens=args.new_tokens,
                decode_chunk=args.decode_chunk, seed=args.seed,
                kv_layout="paged", page_size=args.page_size,
                page_growth=args.page_growth,
                allocator_wait=args.allocator_wait,
                prefill_chunk_tokens=2 * args.page_size,
                attention_impl=impl)
            fa_rows[impl] = got
            fa_streams[impl] = streams
        fused = fa_rows["fused"]
        fused["tokens_match_gather"] = bool(
            fa_streams["fused"] == fa_streams["gather"])
        fused["wall_ratio_vs_gather"] = (
            fused["wall_s"] / fa_rows["gather"]["wall_s"]
            if fa_rows["gather"]["wall_s"] else float("inf"))
        rows["paged_fused"] = {"capacity": k, **fa_rows}
        for impl in ("gather", "fused"):
            r = fa_rows[impl]
            extra = ""
            if impl == "fused":
                extra = (f",tokens_match={r['tokens_match_gather']},"
                         f"wall_ratio={r['wall_ratio_vs_gather']:.2f}x")
            print(f"paged_{impl}_K{k},tok_per_s={r['tok_per_s']:.1f},"
                  f"dispatch_traces={r['dispatch_traces']},"
                  f"dispatch_retraces={r['dispatch_retraces']}{extra}")

    # ---- interleaved-arrivals trace (chunked vs one-shot prefill) ----
    # Long prompts and short decodes arrive interleaved on a page-tight
    # arena: the workload where a whole-prompt prefill at admission both
    # stalls the in-flight decodes for a full dispatch and must afford
    # its entire padded bucket in pages before it can be granted.
    # Chunked admission is bookkeeping (slot + first chunk's page) and
    # the prompt prefills C tokens per round *inside* the decode
    # dispatch, so grants land rounds earlier; the CI gate asserts the
    # p99 queue-wait drop at bit-identical token streams with
    # lock_acquires_per_token not increased.
    if "paged" in layouts and args.kv_layout != "slots":
        k = max(args.capacities)
        il_long = (args.interleaved_long_len
                   if args.interleaved_long_len else 5 * args.page_size)
        il_short = args.page_size
        # two pages per chunk: few enough prefill rounds that chunked
        # admissions/retirements batch as tightly as one-shot's (lock
        # parity), small enough that a long prompt still spreads over
        # several rounds (the interleaving under test)
        il_chunk = 2 * args.page_size
        # decode long enough that slot turnover is decode-dominated in
        # both modes — the regime chunking targets (prefill hidden
        # inside decode rounds), and what keeps per-token lock traffic
        # comparable between the two schedules
        il_new = 2 * args.new_tokens
        rng_il = np.random.default_rng(args.seed + 2)
        il_prompts = [
            rng_il.integers(0, cfg.vocab_size,
                            il_long if i % 2 == 0 else il_short
                            ).astype(np.int32)
            for i in range(args.requests)]
        il_arrivals = poisson_arrival_steps(
            args.requests, k, il_new, max(args.load, 1.2), rng_il)
        # 7/8 of the all-slots worst case: mild page pressure — enough
        # that admission sizing matters (the one-shot path must afford
        # whole padded buckets), not so starved that chunked admission
        # falls to drip-feed single-page grants every round
        il_pages = (7 * k * ((il_long + il_new + 1 + args.page_size - 1)
                             // args.page_size)) // 8
        modes = (("chunked", "unchunked") if args.chunked_prefill == "both"
                 else (("chunked",) if args.chunked_prefill == "on"
                       else ("unchunked",)))
        il_rows, il_streams = {}, {}
        for mode in modes:
            got, streams = bench_slot_engine(
                model, params, il_prompts, il_arrivals, capacity=k,
                new_tokens=il_new, decode_chunk=args.decode_chunk,
                seed=args.seed, kv_layout="paged",
                page_size=args.page_size, page_growth=args.page_growth,
                allocator_wait=args.allocator_wait,
                num_pages=il_pages,
                prefill_chunk_tokens=(il_chunk if mode == "chunked"
                                      else None),
                # this trace isolates chunked-vs-one-shot prefill; the
                # occupancy-bucket ladder would add its own compiles to
                # the gated wall-clock p99 comparison, so pin it off
                bucketed_dispatch="off")
            il_rows[mode] = got
            il_streams[mode] = streams
        if len(modes) == 2:
            ch, un = il_rows["chunked"], il_rows["unchunked"]
            ch["tokens_match_unchunked"] = bool(
                il_streams["chunked"] == il_streams["unchunked"])
            # the latency gate is wall-clock: the step clock never
            # charges one-shot mode for its whole-prompt prefill
            # dispatches (they run inside admission, between rounds),
            # which is exactly the cost chunking removes
            ch["p99_wait_s_drop_vs_unchunked"] = (
                un["p99_wait_s"] / ch["p99_wait_s"]
                if ch["p99_wait_s"] else float("inf"))
            ch["lock_ratio_vs_unchunked"] = (
                ch["lock_acquires_per_token"]
                / un["lock_acquires_per_token"]
                if un["lock_acquires_per_token"] else float("inf"))
            ch["pad_fraction_unchunked"] = un["pad_fraction"]
        rows["interleaved"] = {"capacity": k, "long_len": il_long,
                               "short_len": il_short,
                               "chunk_tokens": il_chunk,
                               "num_pages": il_pages, **il_rows}
        for mode in modes:
            r = il_rows[mode]
            extra = ""
            if mode == "chunked" and "tokens_match_unchunked" in r:
                extra = (f",p99_s_drop="
                         f"{r['p99_wait_s_drop_vs_unchunked']:.2f}x,"
                         f"lock_ratio={r['lock_ratio_vs_unchunked']:.2f},"
                         f"tokens_match={r['tokens_match_unchunked']}")
            print(f"interleaved_{mode}_K{k},"
                  f"tok_per_s={r['tok_per_s']:.1f},"
                  f"p99_wait_s={r['p99_wait_s']:.3f},"
                  f"p99_wait_steps={r['p99_wait_steps']:.1f},"
                  f"pad_fraction={r['pad_fraction']:.3f},"
                  f"lock_per_tok={r['lock_acquires_per_token']:.4f},"
                  f"prefill_chunks={r['prefill_chunks']},"
                  f"stalled_rounds="
                  f"{r['decode_rounds_stalled_by_prefill']}{extra}")

    # ---- multi-turn prefix-cache trace (cache on vs off) ----
    # Shared system prompts plus follow-up turns embedding the parent's
    # actual reply, on the identical trace with the cache on and off.
    # CI gates on the on-row: hits landed, prefill tokens saved, streams
    # bit-identical, zero pages leaked after the drop, lock acquires per
    # round not above the off run.
    if args.prefix_cache == "on" and "paged" in layouts:
        k = max(args.capacities)
        pc_row = bench_prefix_cache(
            model, params, capacity=k, decode_chunk=args.decode_chunk,
            seed=args.seed, page_size=args.page_size,
            vocab=cfg.vocab_size, requests=args.cache_requests,
            fuzz_traces=args.cache_fuzz_traces)
        rows["prefix_cache"] = pc_row
        on = pc_row["on"]
        print(f"prefix_cache_on_K{k},"
              f"cache_hits={on['cache_hits']},"
              f"hit_rate={on['cache_hit_rate']:.2f},"
              f"tokens_served={on['cache_tokens_served']},"
              f"prefill_saved={on['prefill_tokens_saved']},"
              f"pages_donated={on['cache_pages_donated']},"
              f"pages_evicted={on['cache_pages_evicted']},"
              f"lock_ratio_vs_off={on['lock_ratio_vs_off']:.2f},"
              f"tokens_match={on['tokens_match_off']},"
              f"leaked_pages={on['leaked_pages']},"
              f"fuzz_traces_passed={on['fuzz_traces_passed']},"
              f"fifo_ok={on['fifo_ok']}")
        if args.cache_out:
            with open(args.cache_out, "w") as f:
                json.dump(pc_row, f, indent=2)
            print(f"# wrote {args.cache_out}")

    # ---- chaos trace (injected faults vs the fault-free baseline) ----
    # The identical conversation workload fault-free, under transient
    # injected faults, and with one poisoned request. CI gates on the
    # report: faults fired, everything recovered or quarantined, zero
    # leaked pages, survivors bit-identical, lock overhead within 1.05x.
    if args.chaos == "on" and "paged" in layouts:
        k = max(args.capacities)
        chaos_row = bench_chaos(
            model, params, capacity=k, decode_chunk=args.decode_chunk,
            seed=args.seed, page_size=args.page_size,
            vocab=cfg.vocab_size, requests=args.chaos_requests,
            fault_seed=args.fault_seed, alloc_rate=args.fault_rate,
            dispatch_rate=args.fault_rate, stuck_rate=args.fault_rate,
            max_faults=args.chaos_max_faults)
        rows["chaos"] = chaos_row
        ch, po = chaos_row["chaos"], chaos_row["poison"]
        print(f"chaos_K{k},"
              f"faults={ch['faults_injected']},"
              f"by_kind={ch['faults_by_kind']},"
              f"retried={ch['rounds_retried']},"
              f"aborted_batches={ch['aborted_batches']},"
              f"watchdog_trips={ch['watchdog_trips']},"
              f"availability={ch['availability']:.2f},"
              f"lock_ratio={ch['lock_ratio_vs_baseline']:.3f},"
              f"survivors_match={ch['survivor_streams_match_baseline']},"
              f"leaked_pages={ch['leaked_pages']}")
        print(f"chaos_poison_K{k},"
              f"faults={po['faults_injected']},"
              f"quarantined={po['requests_quarantined']},"
              f"failed_rids={po['failed_rids']},"
              f"availability={po['availability']:.2f},"
              f"survivors_match={po['survivor_streams_match_baseline']},"
              f"leaked_pages={po['leaked_pages']}")
        if args.chaos_out:
            with open(args.chaos_out, "w") as f:
                json.dump(chaos_row, f, indent=2)
            print(f"# wrote {args.chaos_out}")

    # ---- open-loop front-end trace (asyncio lifecycle, cancellations)
    # Shared-prefix prompts at capacity, arriving Poisson on the wall
    # clock through the asyncio front-end; every Nth client hangs up
    # mid-stream. The closed-loop driver on the identical engine config
    # supplies the reference streams: survivors must match bit-for-bit,
    # and the drained arena must hold zero leaked pages even though
    # cancelled requests shared refcounted prefix pages with survivors.
    if args.open_loop == "on" and "paged" in layouts:
        k = max(args.capacities)
        ol_groups = max(1, min(args.prefix_groups, k // 2,
                               args.requests))
        ol_prompts = shared_prefix_prompts(
            args.requests, args.prompt_len, ol_groups, cfg.vocab_size,
            np.random.default_rng(args.seed + 4))
        ol_chunk = args.page_size
        # enough decode rounds (>= 4) that a client consuming tokens as
        # they stream can cancel while its request is still in flight —
        # a 2-round generation finishes before any cancel can land
        ol_new = max(4 * args.decode_chunk, args.new_tokens)
        closed, closed_streams = bench_slot_engine(
            model, params, ol_prompts, np.zeros(args.requests),
            capacity=k, new_tokens=ol_new,
            decode_chunk=args.decode_chunk, seed=args.seed,
            kv_layout="paged", page_size=args.page_size,
            prefix_sharing="on", prefill_chunk_tokens=ol_chunk)
        # streams are keyed by rid in submission order (the warm-up
        # request holds the lowest rid and was cleared from finished)
        ordered = [closed_streams[r] for r in sorted(closed_streams)]
        fe_row = bench_open_loop(
            model, params, list(ol_prompts), ordered, capacity=k,
            new_tokens=ol_new, decode_chunk=args.decode_chunk,
            seed=args.seed, page_size=args.page_size,
            prefix_sharing="on", prefill_chunk_tokens=ol_chunk,
            cancel_every=args.cancel_every,
            cancel_after_tokens=args.cancel_after_tokens,
            arrival_rate=args.arrival_rate, slo_ms=args.slo_ms)
        fe_row["closed_loop_tok_per_s"] = closed["tok_per_s"]
        rows["frontend"] = fe_row
        print(f"frontend_open_loop_K{k},"
              f"tok_per_s={fe_row['tok_per_s']:.1f},"
              f"goodput_tok_per_s={fe_row['goodput_tok_per_s']:.1f},"
              f"ttft_p50_ms={fe_row['ttft_p50_ms']:.0f},"
              f"ttft_p99_ms={fe_row['ttft_p99_ms']:.0f},"
              f"slo_attainment={fe_row['slo_attainment']:.2f},"
              f"cancelled={fe_row['cancelled']},"
              f"shed={fe_row['shed']},"
              f"leaked_pages={fe_row['leaked_pages']},"
              f"survivors_match="
              f"{fe_row['survivor_streams_match_closed_loop']},"
              f"fifo_ok={fe_row['fifo_ok']}")
        if args.frontend_out:
            with open(args.frontend_out, "w") as f:
                json.dump(fe_row, f, indent=2)
            print(f"# wrote {args.frontend_out}")

    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"# wrote {args.out}")

    batched = [v for layout in layouts
               for kk, v in rows[layout].items() if int(kk) > 1]
    if batched and not all(v["speedup_vs_legacy"] > 1.0 for v in batched):
        print("# WARNING: batched engine not faster than legacy at K > 1")
    return rows


if __name__ == "__main__":
    main()
