"""Fault-tolerant serving: transactional allocator batches, round-level
recovery, quarantine, and deterministic chaos (DESIGN.md §15).

The correctness bars:

  * **allocator batches are transactions** — an injected fault at *any*
    mutation stage of ``alloc_batch``/``free_batch`` rolls the whole
    batch back (undo log, reverse order), ``check()`` passes, and the
    pool is byte-identical to a never-faulted one (free-list order
    included, so later grants don't diverge);
  * **rounds are transactions** — a failed dispatch rolls the round
    back (the PRNG split is the only host state consumed before the
    jitted call returns) and the retry replays it exactly: survivor
    greedy streams are bit-identical to a fault-free run;
  * **quarantine is surgical** — a request that keeps killing its round
    is removed alone (new ``FAILED`` terminal state, error surfaced on
    its ``StreamHandle`` as ``RequestFailedError`` after its partial
    stream drains); everyone else finishes untouched;
  * **injection is replayable** — a ``FaultPlan`` is a pure function of
    ``(seed, site, occurrence)``; the same seed over the same workload
    injects the same faults.
"""

import asyncio

import numpy as np
import pytest

import jax

from repro.configs import get_arch
from repro.models import build_model
from repro.serve import (
    AsyncFrontend,
    FaultPlan,
    InjectedFault,
    PagePool,
    RequestFailedError,
    RequestState,
    SlotServeEngine,
)
from repro.serve.fuzz import PoolFuzzHarness, drive_trace, gen_trace

#: every stage ``alloc_batch`` journals (kv_pages._fire call sites)
ALLOC_STAGES = ("alloc:validated", "alloc:increfs", "alloc:evict_decrefs",
                "alloc:grant", "alloc:paired_decrefs")


@pytest.fixture(scope="module")
def lm_setup():
    cfg = get_arch("qwen3-14b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _pool_snapshot(pool):
    """Everything observable about a PagePool, for byte-identity checks."""
    return {
        "free": list(pool._free),
        "allocated": pool._allocated.copy(),
        "refcount": pool._refcount.copy(),
        "epoch": pool._epoch.copy(),
        "allocs": pool.allocs, "frees": pool.frees,
        "pages_alloced": pool.pages_alloced,
        "pages_freed": pool.pages_freed,
        "increfs": pool.increfs, "decrefs": pool.decrefs,
        "grant_log": list(pool.grant_log),
    }


def _assert_snapshot_equal(a, b):
    assert a["free"] == b["free"]          # FIFO order, not just the set
    np.testing.assert_array_equal(a["allocated"], b["allocated"])
    np.testing.assert_array_equal(a["refcount"], b["refcount"])
    np.testing.assert_array_equal(a["epoch"], b["epoch"])
    for k in ("allocs", "frees", "pages_alloced", "pages_freed",
              "increfs", "decrefs", "grant_log"):
        assert a[k] == b[k], k


class _StageFault:
    """Raise InjectedFault the first time a chosen stage fires."""

    def __init__(self, stage):
        self.stage = stage
        self.fired = 0

    def __call__(self, stage):
        if stage == self.stage:
            self.fired += 1
            if self.fired == 1:
                raise InjectedFault("alloc", detail=stage)


# ====================================================== pool transactions
@pytest.mark.parametrize("stage", ALLOC_STAGES)
def test_alloc_batch_rolls_back_at_every_stage(stage):
    """A fault at any journaled stage leaves the pool byte-identical —
    including the FIFO free-list order, so a retried batch gets the
    exact pages the faulted attempt briefly held."""
    pool = PagePool(16, 4)
    held = pool.alloc(3, tag="seed")            # live pages for the riders
    shared = pool.alloc(2, tag="shared")
    before = _pool_snapshot(pool)
    pool.fault_hook = _StageFault(stage)
    with pytest.raises(InjectedFault):
        pool.alloc_batch([2, 1], ["a", "b"],
                         incref_groups=[held],
                         paired_decrefs=[held, None],
                         decref_groups=[shared])
    pool.fault_hook = None
    assert pool.aborted_batches == 1
    _assert_snapshot_equal(_pool_snapshot(pool), before)
    pool.check()
    # the retried batch succeeds and grants from the same FIFO head
    out = pool.alloc_batch([2, 1], ["a", "b"],
                           incref_groups=[held],
                           paired_decrefs=[held, None],
                           decref_groups=[shared])
    assert [len(g) for g in out] == [2, 1]
    assert pool.grant_log == ["seed", "shared", "a", "b"]
    pool.check()


def test_free_batch_rolls_back_midway():
    pool = PagePool(12, 4)
    a = pool.alloc(3, "a")
    b = pool.alloc(2, "b")
    before = _pool_snapshot(pool)
    pool.fault_hook = _StageFault("free:decrefs")
    with pytest.raises(InjectedFault):
        pool.free_batch([a, b])
    pool.fault_hook = None
    assert pool.aborted_batches == 1
    _assert_snapshot_equal(_pool_snapshot(pool), before)
    pool.check()
    freed = pool.free_batch([a, b])
    assert sorted(freed) == sorted(a.tolist() + b.tolist())
    assert pool.in_use == 0


def test_faulted_pool_grants_identically_to_clean_pool():
    """Transactionality end to end: interleave faulted (rolled back,
    then retried) batches with clean ones — every grant must equal the
    never-faulted control pool's, page ids included."""
    clean, chaos = PagePool(24, 4), PagePool(24, 4)
    fp = FaultPlan(5, alloc_rate=0.4)
    chaos.fault_hook = fp.alloc_hook
    rng = np.random.default_rng(2)
    live_clean, live_chaos = [], []
    for step in range(30):
        if live_clean and (clean.n_free < 4 or rng.random() < 0.4):
            i = rng.integers(len(live_clean))
            clean.free_batch([live_clean.pop(i)])
            grp = live_chaos.pop(i)
            try:
                chaos.free_batch([grp])
            except InjectedFault:
                with fp.suspended():
                    chaos.free_batch([grp])
        else:
            n = int(rng.integers(1, 4))
            g_clean = clean.alloc_batch([n], [step])[0]
            try:
                g_chaos = chaos.alloc_batch([n], [step])[0]
            except InjectedFault:
                with fp.suspended():
                    g_chaos = chaos.alloc_batch([n], [step])[0]
            np.testing.assert_array_equal(g_clean, g_chaos)
            live_clean.append(g_clean)
            live_chaos.append(g_chaos)
        chaos.check()
    assert fp.injected > 0
    assert chaos.aborted_batches > 0
    assert list(chaos._free) == list(clean._free)


def test_stuck_holder_trips_the_watchdog():
    """A slow holder (injected sleep inside the critical section) must
    trip the armed lock watchdog but complete normally."""
    pool = PagePool(8, 4, watchdog_s=0.002)
    fp = FaultPlan(0, stuck_rate=1.0, stuck_hold_s=0.01, max_faults=2)
    pool.fault_hook = fp.alloc_hook
    g = pool.alloc(2, "slow")
    assert fp.stuck_holds > 0
    assert pool.mutex.lock_stats()["watchdog_trips"] >= 1
    pool.fault_hook = None
    pool.free_batch([g])
    pool.check()


# ======================================================== plan determinism
def test_fault_plan_is_replayable_and_suspendable():
    kw = dict(alloc_rate=0.3, dispatch_rate=0.2, executor_rate=0.2)
    a, b = FaultPlan(7, **kw), FaultPlan(7, **kw)
    log_a, log_b = [], []
    for plan, log in ((a, log_a), (b, log_b)):
        for k in range(40):
            site = ("alloc", "dispatch", "executor")[k % 3]
            try:
                if site == "alloc":
                    plan.alloc_hook("alloc:grant")
                elif site == "dispatch":
                    plan.dispatch([k])
                else:
                    plan.executor()
                log.append(None)
            except InjectedFault as e:
                log.append(e.kind)
    assert log_a == log_b                   # same seed, same schedule
    assert a.injected == b.injected > 0
    assert a.by_kind == b.by_kind
    # a different seed gives a different schedule
    c = FaultPlan(8, **kw)
    log_c = []
    for k in range(40):
        site = ("alloc", "dispatch", "executor")[k % 3]
        try:
            (c.alloc_hook("alloc:grant") if site == "alloc"
             else c.dispatch([k]) if site == "dispatch" else c.executor())
            log_c.append(None)
        except InjectedFault as e:
            log_c.append(e.kind)
    assert log_c != log_a
    # suspension silences every site without consuming draws
    d = FaultPlan(7, **kw)
    with d.suspended():
        for _ in range(20):
            d.alloc_hook("alloc:grant")
            d.dispatch([1])
            d.executor()
    assert d.injected == 0 and d._draws == {}


def test_fault_plan_budget_and_poison():
    fp = FaultPlan(0, poison_rid=4, max_faults=2)
    for _ in range(2):
        with pytest.raises(InjectedFault) as ei:
            fp.dispatch([1, 4, 9])
        assert ei.value.rid == 4
    fp.dispatch([1, 4, 9])                  # budget exhausted: silent
    fp.dispatch([1, 9])                     # poisoned rid absent: silent
    assert fp.injected == 2


# ===================================================== engine round recovery
def _chaos_engine(model, params, fault_plan):
    return SlotServeEngine(model, params, capacity=3, max_len=128,
                           kv_layout="paged", page_size=4, seed=0,
                           prefix_cache="on", prefill_chunk_tokens=4,
                           decode_chunk=2, fault_plan=fault_plan,
                           quarantine_after=3, retry_backoff_s=0.0)


def _drive(model, params, fault_plan, *, vocab, trace_seed=7):
    events = gen_trace(trace_seed, n_requests=6, vocab=vocab,
                       max_prompt=12, max_new=6, p_cancel=0.0)
    eng = _chaos_engine(model, params, fault_plan)
    res = drive_trace(eng, events)
    st = eng.stats()
    eng.drop_prefix_cache()
    eng.pool.check()
    assert eng.pool.pages.in_use == 0       # leak-free drain, every run
    return res, st, eng


def _survivors_match(base, res):
    matched = 0
    for rid, a in base.items():
        b = res.get(rid)
        if b is None or a["cancelled"] or b["cancelled"]:
            continue
        if not np.array_equal(a["prompt"], b["prompt"]):
            continue
        assert a["out"] == b["out"], f"rid {rid} survivor stream diverged"
        matched += 1
    return matched


def test_round_retry_preserves_survivor_streams(lm_setup):
    """Random allocator + dispatch faults: every round either commits or
    rolls back and retries, so all requests finish with greedy streams
    bit-identical to the fault-free run, and the drain is leak-free."""
    cfg, model, params = lm_setup
    base, base_st, _ = _drive(model, params, None, vocab=cfg.vocab_size)
    assert base_st["faults_injected"] == 0
    assert base_st["rounds_retried"] == 0

    fp = FaultPlan(31, alloc_rate=0.08, dispatch_rate=0.05)
    res, st, _ = _drive(model, params, fp, vocab=cfg.vocab_size)
    assert fp.injected > 0                  # the chaos actually happened
    assert st["rounds_retried"] > 0
    assert st["requests_quarantined"] == 0  # transient faults never kill
    assert st["aborted_batches"] > 0
    assert _survivors_match(base, res) == len(base)


def test_dispatch_fault_leaves_the_donated_arena_intact(lm_setup):
    """The round program takes the arena over (donation), so a dispatch
    fault must strike before the call: the retried round then reads an
    intact arena. Dispatch faults alone: every retried round replays the
    fault-free run's tokens, the pool's arena is live after every round,
    ``check()`` passes, and every dispatch that ran took the arena over
    (``arena_in_place`` 1.0)."""
    cfg, model, params = lm_setup
    base, _, _ = _drive(model, params, None, vocab=cfg.vocab_size)

    fp = FaultPlan(11, dispatch_rate=0.3)
    events = gen_trace(7, n_requests=6, vocab=cfg.vocab_size,
                       max_prompt=12, max_new=6, p_cancel=0.0)
    eng = _chaos_engine(model, params, fp)
    step = eng.step

    def checked_step():
        n = step()
        assert not any(x.is_deleted()
                       for x in jax.tree_util.tree_leaves(eng.pool.arena))
        eng.pool.check()
        return n
    eng.step = checked_step
    res = drive_trace(eng, events)
    st = eng.stats()
    assert fp.injected > 0 and st["rounds_retried"] > 0
    assert st["requests_quarantined"] == 0
    assert st["arena_in_place"] == 1.0
    assert _survivors_match(base, res) == len(base)
    eng.drop_prefix_cache()
    eng.pool.check()
    assert eng.pool.pages.in_use == 0


def test_poisoned_request_is_quarantined_alone(lm_setup):
    """A request that deterministically kills its round is FAILED after
    ``quarantine_after`` consecutive failures; every other request's
    stream is bit-identical to the fault-free run."""
    cfg, model, params = lm_setup
    base, _, _ = _drive(model, params, None, vocab=cfg.vocab_size)

    fp = FaultPlan(0, poison_rid=2)
    res, st, eng = _drive(model, params, fp, vocab=cfg.vocab_size)
    assert st["requests_quarantined"] == 1
    assert st["failed"] == 1
    failed = [r for r in eng.finished if r.state is RequestState.FAILED]
    assert len(failed) == 1
    assert failed[0].rid == 2
    assert "injected fault" in failed[0].error
    assert st["rounds_retried"] >= eng.quarantine_after
    survivors = _survivors_match(base, res)
    assert survivors == len(base) - 1       # everyone but the poisoned rid


def test_device_errors_propagate_without_retry(lm_setup):
    """Only injected faults are transient. A compile error or exhausted
    device memory inside a round propagates out of ``step`` on the
    first attempt: no retry, no quarantine, nothing reported FAILED."""
    cfg, model, params = lm_setup
    eng = SlotServeEngine(model, params, capacity=2, max_len=64,
                          kv_layout="paged", page_size=4, seed=0,
                          decode_chunk=2)
    calls = []

    def exhausted(*args, **kwargs):
        calls.append(1)
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: out of memory allocating the round")

    for name in ("_chunk", "_round", "_bucket_chunk", "_bucket_round"):
        setattr(eng, name, exhausted)
    eng.submit(np.arange(1, 7), max_new_tokens=4)
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXH"):
        eng.step()
    assert calls == [1]
    assert eng.rounds_retried == 0
    assert eng.requests_quarantined == 0
    assert not [r for r in eng.finished if r.state is RequestState.FAILED]


def test_engine_watchdog_counts_stuck_holders(lm_setup):
    """`allocator_watchdog_s` arms the pool mutex; a stuck-holder fault
    plan must surface `watchdog_trips` in engine stats."""
    cfg, model, params = lm_setup
    fp = FaultPlan(1, stuck_rate=1.0, stuck_hold_s=0.01, max_faults=3)
    eng = SlotServeEngine(model, params, capacity=2, max_len=64,
                          kv_layout="paged", page_size=4, seed=0,
                          decode_chunk=2, fault_plan=fp,
                          allocator_watchdog_s=0.002)
    rng = np.random.default_rng(0)
    for _ in range(2):
        eng.submit(rng.integers(1, cfg.vocab_size, 6), max_new_tokens=4)
    while eng.queue or eng.active:
        eng.step()
    st = eng.stats()
    assert fp.stuck_holds > 0
    assert st["watchdog_trips"] >= 1
    assert st["finished"] == 2              # slow, not broken


# ========================================================== async front-end
def test_frontend_survives_executor_death(lm_setup):
    """Injected executor deaths fire before the engine step starts, so
    the frontend just retries the round: every stream completes."""
    cfg, model, params = lm_setup
    rng = np.random.default_rng(0)
    # seed 3's first executor draw fires at rate 0.25
    fp = FaultPlan(3, executor_rate=0.25)
    eng = SlotServeEngine(model, params, capacity=2, max_len=64,
                          kv_layout="paged", page_size=4, seed=0,
                          decode_chunk=2, fault_plan=fp)

    async def main():
        async with AsyncFrontend(eng) as fe:
            hs = [await fe.submit(rng.integers(1, cfg.vocab_size, 5), 4)
                  for _ in range(4)]
            outs = [await h.collect() for h in hs]
        return fe, outs

    fe, outs = asyncio.run(main())
    assert fe.executor_faults > 0
    assert all(len(o) == 4 for o in outs)
    assert fe.stats()["frontend_executor_faults"] == fe.executor_faults


def test_frontend_surfaces_quarantine_as_request_failed(lm_setup):
    """A quarantined request's handle delivers its partial stream, then
    raises RequestFailedError; concurrent handles stream to completion."""
    cfg, model, params = lm_setup
    rng = np.random.default_rng(0)
    fp = FaultPlan(0, poison_rid=1)
    eng = SlotServeEngine(model, params, capacity=2, max_len=64,
                          kv_layout="paged", page_size=4, seed=0,
                          decode_chunk=2, fault_plan=fp,
                          quarantine_after=2, retry_backoff_s=0.0)

    async def main():
        async with AsyncFrontend(eng) as fe:
            h0 = await fe.submit(rng.integers(1, cfg.vocab_size, 5), 4)
            h1 = await fe.submit(rng.integers(1, cfg.vocab_size, 5), 4)
            out0 = await h0.collect()
            with pytest.raises(RequestFailedError) as ei:
                await h1.collect()
        return h1, out0, str(ei.value)

    h1, out0, msg = asyncio.run(main())
    assert h1.state is RequestState.FAILED
    assert "injected fault" in msg
    assert len(out0) == 4                   # the survivor is whole
    eng.pool.check()
    assert eng.pool.pages.in_use == 0


# =============================================================== fuzz tier
def test_pool_fuzz_with_allocator_faults():
    """The lifecycle fuzz harness under injected allocator aborts: every
    abort is recovered (rollback + compensating eviction replay) and the
    arena still drains empty."""
    injected = recovered = 0
    for seed in range(20):
        fp = FaultPlan(seed, alloc_rate=0.1)
        h = PoolFuzzHarness(seed, num_pages=48, page_size=4, cache=True,
                            faults=fp)
        h.run(rounds=30)
        assert h.pool.in_use == 0
        injected += fp.injected
        recovered += h.aborts_recovered
    assert injected > 0
    assert recovered > 0


# ====================================================== launch leak gate
def test_launch_leak_gate_fails_loudly_on_leak(lm_setup, capsys):
    """The launch driver's post-drain gate: a drained engine passes; a
    page held past drain (cache dropped first, so retention doesn't
    mask it) exits non-zero instead of printing a number nobody reads."""
    from repro.launch.serve import enforce_leak_gate

    cfg, model, params = lm_setup
    eng = _chaos_engine(model, params, None)
    enforce_leak_gate(eng)                       # clean drain: no exit
    assert "leak check: OK" in capsys.readouterr().out

    eng.pool.pages.alloc(1)                      # simulate a leaked page
    with pytest.raises(SystemExit) as ei:
        enforce_leak_gate(eng)
    assert ei.value.code == 1
    assert "FATAL" in capsys.readouterr().out


def test_launch_exits_nonzero_on_failure_without_fault_plan(lm_setup,
                                                            capsys):
    """Without a fault plan a FAILED request means a real error was
    absorbed: the launch driver exits non-zero. Under a fault plan
    quarantine is the designed outcome and the run goes on."""
    from repro.launch.serve import enforce_no_failures

    cfg, model, params = lm_setup
    _, _, eng = _drive(model, params, None, vocab=cfg.vocab_size)
    enforce_no_failures(eng)                     # all FINISHED: no exit
    eng.finished[0].state = RequestState.FAILED
    with pytest.raises(SystemExit) as ei:
        enforce_no_failures(eng)
    assert ei.value.code == 1
    assert "FATAL" in capsys.readouterr().out
    eng.fault_plan = FaultPlan(0)
    enforce_no_failures(eng)
