"""Backend-unified sync API: registry, selection triples, windowed
planning, host-classification caching, and the cross-backend equivalence
properties (host threading vs Pallas-interpret kernel vs pure-jnp ref)
for all three primitives."""

import ast
import inspect
import warnings

import numpy as np
import pytest

try:  # hypothesis is optional in this image (tests/_hypothesis_compat.py)
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, st

from repro.core.abstraction import (FERMI, TESLA, TPU_V5E, PrimitiveKind,
                                    select_backend, select_impl)
from repro.core.hostsync import SleepingSemaphore, SpinSemaphore, XFBarrier
from repro.sync import (SyncBackend, SyncLibrary, SyncTimeoutError,
                        WindowedPlanner, available_backends, get_backend,
                        register_backend)
from repro.sync import library as sync_library

BACKENDS = ("host", "kernel", "ref")


@pytest.fixture
def lib():
    return SyncLibrary.host_default()


# ----------------------------------------------------------------- registry
def test_builtin_backends_registered(monkeypatch):
    assert set(available_backends()) >= {"host", "kernel", "ref"}
    assert get_backend("kernel").fast_plans
    assert not get_backend("host").fast_plans
    with pytest.raises(KeyError):
        get_backend("no-such-backend")
    # the kernel backend's tier follows the platform
    assert [get_backend(n).tier for n in ("host", "kernel", "ref")] \
        == ["host", "interpret", "ref"]
    import repro.kernels
    monkeypatch.setattr(repro.kernels, "default_interpret", lambda: False)
    assert get_backend("kernel").tier == "compiled"


def test_register_custom_backend(lib):
    class Recording(SyncBackend):
        fast_plans = True

        def plan_semaphore(self, arrivals, holds, capacity, *, window=None):
            n = len(arrivals)
            z = np.zeros(n, np.float32)
            return z, z, np.zeros(n, np.int32), None

    register_backend("custom-test", Recording())
    try:
        plan = lib.plan_semaphore([0.0, 1.0], [1.0, 1.0], 1,
                                  backend="custom-test")
        assert plan.backend == "custom-test"
        # live constructors fall back to the host substrate
        sem = get_backend("custom-test").semaphore(
            2, "sleeping", lib.choice(PrimitiveKind.SEMAPHORE).strategy)
        assert isinstance(sem, SleepingSemaphore)
    finally:
        from repro.sync.backends import _REGISTRY
        _REGISTRY.pop("custom-test", None)


# ---------------------------------------------------------- selection triple
def test_selection_triple_backend_axis():
    assert select_backend(TPU_V5E) == "kernel"
    assert select_backend(TESLA) == "kernel"
    assert select_backend(sync_library.HOST_NOMINAL) == "host"
    # select_impl carries the backend in the triple, overridable
    c = select_impl(TPU_V5E, PrimitiveKind.SEMAPHORE)
    assert (c.backend, c.algorithm) == ("kernel", "sleeping")
    c = select_impl(FERMI, PrimitiveKind.MUTEX, backend="ref")
    assert (c.backend, c.algorithm) == ("ref", "spin_backoff")


def test_library_pins_override_selection(lib):
    spin_lib = SyncLibrary.host_default(semaphore_kind="spin")
    assert isinstance(spin_lib.semaphore(2), SpinSemaphore)
    assert isinstance(lib.semaphore(2), SleepingSemaphore)
    assert isinstance(lib.barrier(3), XFBarrier)
    tpu_lib = SyncLibrary(machine=TPU_V5E)
    assert tpu_lib.backend_name() == "kernel"
    # live-only fallback: plans on a pinned "host" library use the kernel
    assert SyncLibrary.host_default(backend="host") \
        .planning_backend_name() == "kernel"
    assert SyncLibrary.host_default(backend="ref") \
        .planning_backend_name() == "ref"


# ----------------------------------------------------------- windowed plans
def test_windowed_planner_buckets_and_warns_once():
    planner = WindowedPlanner(
        plan=lambda a: (a,),
        pad=lambda arrays, n, w: (np.pad(arrays[0], (0, w - n)),),
        base_window=8, name="test_planner")
    assert planner.window_for(5) == 8
    assert planner.window_for(9) == 16
    assert planner.window_for(33) == 64
    (out,) = planner(np.arange(6, dtype=np.float32))
    assert out.shape == (6,)

    planner2 = WindowedPlanner(
        plan=lambda a: (a,),
        pad=lambda arrays, n, w: (np.pad(arrays[0], (0, w - n)),),
        base_window=4, name="warn_planner")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        planner2(np.arange(7, dtype=np.float32))
        planner2(np.arange(9, dtype=np.float32))
    msgs = [w for w in rec if issubclass(w.category, RuntimeWarning)]
    assert len(msgs) == 1  # one-time warning, not once per call


def test_window_overflow_every_boundary_matches_ref():
    """Overflow bucketing is exact at and around every power-of-2
    boundary of the base window, for all three kernel families: the
    bucketed plan equals the unbucketed ref plan at each trace length
    straddling w, 2w, 4w, 8w (one below, at, and one above)."""
    from repro.kernels.semaphore.ops import (semaphore_admission,
                                             semaphore_admission_window)
    from repro.kernels.ticket_lock.ops import (ticket_lock_run,
                                               ticket_lock_window)
    from repro.kernels.xf_barrier.ops import xf_barrier, xf_barrier_window
    import jax.numpy as jnp

    w = 4
    boundaries = sorted({n for bucket in (w, 2 * w, 4 * w, 8 * w)
                         for n in (bucket - 1, bucket, bucket + 1)})
    rng = np.random.default_rng(11)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        for n in boundaries:
            arr = np.sort(rng.uniform(0, 4, n)).astype(np.float32)
            hold = rng.uniform(0.5, 2, n).astype(np.float32)
            gw, rw_, ww = semaphore_admission_window(
                arr, hold, capacity=2, window=w, use_kernel=False)
            assert gw.shape == (n,)
            g, r, wtd = semaphore_admission(
                jnp.asarray(arr), jnp.asarray(hold), capacity=2,
                use_kernel=False)
            np.testing.assert_allclose(gw, np.asarray(g), rtol=1e-6)
            np.testing.assert_allclose(rw_, np.asarray(r), rtol=1e-6)
            np.testing.assert_array_equal(ww, np.asarray(wtd))

            arrival = rng.permutation(n).astype(np.int32)
            m = rng.uniform(0.5, 1.5, n).astype(np.float32)
            b = rng.normal(size=n).astype(np.float32)
            go, to, acc = ticket_lock_window(arrival, m, b, window=w,
                                             use_kernel=False)
            g2, t2, acc2 = ticket_lock_run(
                jnp.asarray(arrival), jnp.asarray(m), jnp.asarray(b),
                use_kernel=False)
            np.testing.assert_array_equal(go, np.asarray(g2))
            np.testing.assert_array_equal(to, np.asarray(t2))
            np.testing.assert_allclose(float(acc), float(acc2), rtol=2e-4)

            present = (rng.uniform(size=n) < 0.7).astype(np.int32)
            required = (rng.uniform(size=n) < 0.8).astype(np.int32)
            flags = np.zeros(n, np.int32)
            aw, relw, dw, sw = xf_barrier_window(
                flags, 1, present, required, window=w, use_kernel=False)
            a, rel, d, s = xf_barrier(
                jnp.asarray(flags), jnp.int32(1), jnp.asarray(present),
                jnp.asarray(required), use_kernel=False)
            np.testing.assert_array_equal(aw, np.asarray(a))
            np.testing.assert_array_equal(relw, np.asarray(rel))
            assert int(dw) == int(d)
            np.testing.assert_array_equal(sw, np.asarray(s))


def test_window_overflow_warning_fires_once_per_planner():
    """The one-time-warning contract: a planner warns on its *first*
    overflow only — later overflows, even into different buckets, are
    silent; a second planner instance gets its own first warning."""
    def fresh():
        return WindowedPlanner(
            plan=lambda a: (a,),
            pad=lambda arrays, n, w: (np.pad(arrays[0], (0, w - n)),),
            base_window=4, name="overflow_planner")

    p1, p2 = fresh(), fresh()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        p1(np.arange(5, dtype=np.float32))      # -> bucket 8: warns
        p1(np.arange(17, dtype=np.float32))     # -> bucket 32: silent
        p1(np.arange(9, dtype=np.float32))      # -> bucket 16: silent
        p2(np.arange(6, dtype=np.float32))      # fresh planner: warns
        p2(np.arange(3, dtype=np.float32))      # within window: silent
    msgs = [w for w in rec if issubclass(w.category, RuntimeWarning)]
    assert len(msgs) == 2
    assert all("overflow_planner" in str(w.message) for w in msgs)


def test_ticket_and_barrier_windowed_match_unwindowed():
    from repro.kernels.ticket_lock.ops import (ticket_lock_run,
                                               ticket_lock_window)
    from repro.kernels.xf_barrier.ops import xf_barrier, xf_barrier_window
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    n = 11
    arrival = rng.permutation(n).astype(np.int32)
    m = rng.uniform(0.5, 1.5, n).astype(np.float32)
    b = rng.normal(size=n).astype(np.float32)
    gw, tw, accw = ticket_lock_window(arrival, m, b, window=8)
    g, t, acc = ticket_lock_run(jnp.asarray(arrival), jnp.asarray(m),
                                jnp.asarray(b))
    np.testing.assert_array_equal(gw, np.asarray(g))
    np.testing.assert_array_equal(tw, np.asarray(t))
    np.testing.assert_allclose(float(accw), float(acc), rtol=2e-4)

    present = (rng.uniform(size=n) < 0.7).astype(np.int32)
    required = (rng.uniform(size=n) < 0.8).astype(np.int32)
    flags = np.zeros(n, np.int32)
    aw, rw, dw, sw = xf_barrier_window(flags, 1, present, required,
                                       window=8)
    a, r, d, s = xf_barrier(jnp.asarray(flags), jnp.int32(1),
                            jnp.asarray(present), jnp.asarray(required))
    np.testing.assert_array_equal(aw, np.asarray(a))
    np.testing.assert_array_equal(rw, np.asarray(r))
    assert int(dw) == int(d)
    np.testing.assert_array_equal(sw, np.asarray(s))


# ------------------------------------------------------- for_host() caching
def test_for_host_probe_cached_with_refresh_escape(monkeypatch):
    calls = {"n": 0}

    def fake_probe(**kw):
        calls["n"] += 1
        return sync_library.HOST_NOMINAL

    import repro.core.hostbench_probe as probe_mod
    monkeypatch.setattr(probe_mod, "classify_host", fake_probe)
    monkeypatch.setattr(sync_library, "_HOST_MACHINES", {})

    SyncLibrary.for_host()
    SyncLibrary.for_host()
    SyncLibrary.for_host()
    assert calls["n"] == 1          # probe ran once, result cached
    SyncLibrary.for_host(refresh=True)
    assert calls["n"] == 2          # explicit escape hatch re-probes
    SyncLibrary.for_host(threads=2)
    SyncLibrary.for_host(threads=2)
    assert calls["n"] == 3          # distinct probe params, distinct entry


# -------------------------------------------- cross-backend equivalence
@settings(max_examples=5, deadline=None)
@given(n=st.integers(4, 12), cap=st.integers(1, 4),
       seed=st.integers(0, 10_000))
def test_semaphore_plans_equivalent_across_backends(n, cap, seed):
    """Property: the real Algorithm-5 host semaphore (threads, observed),
    the Pallas kernel, and the jnp oracle produce the same grant order,
    waited set, and release timeline on a random trace."""
    # built per example: Hypothesis rejects function-scoped fixtures
    lib = SyncLibrary.host_default()
    rng = np.random.default_rng(seed)
    arrivals = np.sort(rng.uniform(0, 3, n)).astype(np.float32)
    holds = rng.uniform(1, 3, n).astype(np.float32)
    plans = {be: lib.plan_semaphore(arrivals, holds, cap, backend=be)
             for be in BACKENDS}
    ref = plans["ref"]
    for be, plan in plans.items():
        np.testing.assert_array_equal(plan.waited, ref.waited, err_msg=be)
        np.testing.assert_array_equal(plan.grant_order, ref.grant_order,
                                      err_msg=be)
        np.testing.assert_allclose(plan.grant, ref.grant, rtol=1e-5,
                                   atol=1e-5, err_msg=be)
        np.testing.assert_allclose(plan.release, ref.release, rtol=1e-5,
                                   atol=1e-5, err_msg=be)
    # occupancy never exceeds K on the shared timeline
    g, r = ref.grant, ref.release
    for i in range(n):
        assert np.sum((g <= g[i] + 1e-6) & (r > g[i] + 1e-6)) <= cap


@settings(max_examples=5, deadline=None)
@given(n=st.integers(2, 10), seed=st.integers(0, 10_000))
def test_mutex_plans_equivalent_across_backends(n, seed):
    """Property: real TicketMutex threads under contention grant in the
    same FIFO order — and serialize the same order-sensitive affine
    chain — as the kernel and the oracle."""
    # built per example: Hypothesis rejects function-scoped fixtures
    lib = SyncLibrary.host_default()
    rng = np.random.default_rng(seed)
    arrival = rng.permutation(n).astype(np.int32)
    m = rng.uniform(0.5, 1.5, n).astype(np.float32)
    b = rng.normal(size=n).astype(np.float32)
    plans = {be: lib.plan_mutex(arrival, m, b, backend=be)
             for be in BACKENDS}
    ref = plans["ref"]
    for be, plan in plans.items():
        np.testing.assert_array_equal(plan.grant_order, ref.grant_order,
                                      err_msg=be)
        np.testing.assert_array_equal(plan.turn_trace, ref.turn_trace,
                                      err_msg=be)
        np.testing.assert_allclose(plan.acc, ref.acc, rtol=2e-4,
                                   atol=1e-4, err_msg=be)
        assert plan.fifo


@settings(max_examples=5, deadline=None)
@given(n=st.integers(2, 12), seed=st.integers(0, 10_000))
def test_barrier_plans_equivalent_across_backends(n, seed):
    """Property: one XF-barrier epoch completes/stalls identically —
    done bit, straggler bitmap, release flags on required slots — on
    real threads, the kernel, and the oracle."""
    # built per example: Hypothesis rejects function-scoped fixtures
    lib = SyncLibrary.host_default()
    rng = np.random.default_rng(seed)
    present = (rng.uniform(size=n) < 0.8).astype(np.int64)
    required = (rng.uniform(size=n) < 0.8).astype(np.int64)
    plans = {be: lib.plan_barrier(present, required, epoch=1, backend=be)
             for be in BACKENDS}
    ref = plans["ref"]
    expect_done = int(np.all(present[required > 0]))
    for be, plan in plans.items():
        assert plan.done == ref.done == expect_done, be
        np.testing.assert_array_equal(plan.stragglers, ref.stragglers,
                                      err_msg=be)
        np.testing.assert_array_equal(plan.released, ref.released,
                                      err_msg=be)
        np.testing.assert_array_equal(
            plan.straggler_ranks,
            np.flatnonzero((required > 0) & (present == 0)), err_msg=be)


# ------------------------------------------------- bounded waits (§15)
def test_live_mutex_timeout_burns_ticket_and_recovers(lib):
    """``SyncLibrary.acquire(timeout=)`` raises a typed error when the
    budget expires, and the burned ticket leaves the mutex consistent:
    the FIFO turn passes on and the lock is takeable again."""
    import threading
    import time

    m = lib.mutex(kind="ticket")
    assert SyncLibrary.try_acquire(m)        # uncontended: granted at once
    m.unlock()

    m.lock()
    res = {}

    def waiter():
        try:
            SyncLibrary.acquire(m, timeout=0.01, what="waiter")
            res["r"] = "acquired"
            m.unlock()
        except SyncTimeoutError as e:
            res["r"] = "timeout"
            res["e"] = e

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)          # budget expires while we still hold
    m.unlock()                # burned-ticket discipline: the waiter
    t.join()                  # takes its turn, passes it on, reports F
    assert res["r"] == "timeout"
    assert res["e"].timeout_s == 0.01
    assert isinstance(res["e"], TimeoutError)
    # the turn was passed on, not wedged: the mutex is free again
    assert SyncLibrary.try_acquire(m)
    m.unlock()
    # unbounded form never raises
    SyncLibrary.acquire(m)
    m.unlock()


def test_live_semaphore_timeout_rolls_count_back(lib):
    """A timed-out semaphore wait must roll its count back — the slot it
    briefly claimed stays available to the next acquirer."""
    import threading
    import time

    sem = lib.semaphore(1)
    SyncLibrary.acquire(sem)                 # hold the only slot
    res = {}

    def waiter():
        res["ok"] = SyncLibrary.try_acquire(sem)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.02)
    sem.post()                # deliver the turn; expired waiter rolls back
    t.join()
    assert res["ok"] is False
    SyncLibrary.acquire(sem, timeout=1.0)    # rolled-back slot still there
    sem.post()


@settings(max_examples=5, deadline=None)
@given(n=st.integers(3, 10), seed=st.integers(0, 10_000))
def test_bounded_mutex_plans_match_oracle_across_backends(n, seed):
    """Property: the bounded-wait mutex timeline — who acquired, who
    burned its ticket, the shared turn clock — agrees with the
    step-exact numpy oracle on host (observed execution), kernel, and
    ref alike."""
    # built per example: Hypothesis rejects function-scoped fixtures
    lib = SyncLibrary.host_default()
    from repro.kernels.ticket_lock.ops import ticket_lock_bounded_oracle

    rng = np.random.default_rng(seed)
    arrivals = np.sort(rng.uniform(0, 2, n)).astype(np.float32)
    holds = rng.uniform(0.5, 1.5, n).astype(np.float32)
    timeouts = rng.choice(
        [0.0, 0.7, 2.5, np.inf], size=n).astype(np.float32)
    g_ref, grant_ref, rel_ref = ticket_lock_bounded_oracle(
        arrivals, holds, timeouts)
    assert g_ref.any()                        # trace exercises both fates
    for be in BACKENDS:
        plan = lib.plan_mutex_bounded(arrivals, holds, timeouts,
                                      backend=be)
        np.testing.assert_array_equal(plan.granted, g_ref, err_msg=be)
        np.testing.assert_allclose(plan.grant, grant_ref, rtol=1e-4,
                                   atol=1e-3, err_msg=be)
        np.testing.assert_allclose(plan.release, rel_ref, rtol=1e-4,
                                   atol=1e-3, err_msg=be)
        assert 1 <= plan.iterations <= n + 2
        np.testing.assert_array_equal(
            plan.timed_out, np.flatnonzero(~g_ref), err_msg=be)
    # all-unbounded degenerates to the plain FIFO mutex timeline
    free = lib.plan_mutex_bounded(arrivals, holds,
                                  np.full(n, np.inf, np.float32),
                                  backend="ref")
    assert free.granted.all()


# ----------------------------------------------------- serve-stack injection
def test_serve_stack_has_no_direct_primitive_imports():
    """Acceptance criterion: engine/scheduler reach primitives only
    through the injected SyncLibrary."""
    import repro.serve.engine as engine_mod
    import repro.serve.scheduler as scheduler_mod
    for mod in (engine_mod, scheduler_mod):
        tree = ast.parse(inspect.getsource(mod))
        imported = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                imported.update(a.name for a in node.names)
            elif isinstance(node, ast.ImportFrom):
                imported.add(node.module or "")
        for name in imported:
            assert "hostsync" not in name, (mod.__name__, name)
            assert "kernels" not in name, (mod.__name__, name)


def test_admission_controller_takes_injected_library():
    from repro.serve.scheduler import AdmissionController
    ctl = AdmissionController(
        2, lib=SyncLibrary.host_default(semaphore_kind="spin"))
    assert ctl.kind == "SpinSemaphore"
    assert ctl.acquire_slot(timeout=1.0)
    ctl.release_slot()
    ctl_default = AdmissionController(2)
    assert ctl_default.kind == "SleepingSemaphore"


def test_plan_admission_backend_flows_through():
    from repro.serve.scheduler import plan_admission
    arrivals = np.arange(6, dtype=np.float32) * 0.1
    service = np.full(6, 2.0, np.float32)
    p_def = plan_admission(arrivals, service, capacity=2)
    p_ref = plan_admission(arrivals, service, capacity=2,
                           lib=SyncLibrary.host_default(backend="ref"))
    assert p_def.backend == "kernel" and p_ref.backend == "ref"
    np.testing.assert_allclose(p_def.grant, p_ref.grant, rtol=1e-6)
    assert p_def.waited[:2].sum() == 0 and p_def.waited[2:].sum() == 4


# ------------------------------------- contention-adaptive wait strategy
def test_select_wait_strategy_follows_paper_guidelines():
    from repro.core.abstraction import (WaitStrategy, classify,
                                        select_wait_strategy)
    from repro.sync.library import HOST_NOMINAL
    assert classify(HOST_NOMINAL) == "balanced"
    # balanced machine: spin when uncontended, backoff at moderate
    # contention, bounded-atomics sleep when saturated
    assert select_wait_strategy(HOST_NOMINAL, 0.0) is WaitStrategy.SPIN
    assert (select_wait_strategy(HOST_NOMINAL, 0.3)
            is WaitStrategy.SPIN_BACKOFF)
    assert select_wait_strategy(HOST_NOMINAL, 0.9) is WaitStrategy.SLEEP
    # tesla-class: contentious atomics are 10-90x volatile — give up on
    # spinning almost immediately
    assert select_wait_strategy(TESLA, 0.01) is WaitStrategy.SPIN
    assert select_wait_strategy(TESLA, 0.05) is WaitStrategy.SLEEP
    # fermi-class line hostage punishes tight polling: backoff even at
    # saturation (paper: spin+backoff is the best Fermi mutex)
    assert (select_wait_strategy(FERMI, 0.9)
            is WaitStrategy.SPIN_BACKOFF)
    # no atomics to retry: polling volatile flags is all there is
    assert select_wait_strategy(TPU_V5E, 0.0) is WaitStrategy.SLEEP
    # out-of-range inputs clamp instead of raising
    assert select_wait_strategy(HOST_NOMINAL, -1.0) is WaitStrategy.SPIN
    assert select_wait_strategy(HOST_NOMINAL, 7.0) is WaitStrategy.SLEEP


def test_adaptive_mutex_retunes_between_rounds(lib):
    from repro.core.abstraction import WaitStrategy
    from repro.core.hostsync import AdaptiveMutex, TicketMutex
    m = lib.mutex(kind="adaptive", expected_contention=0.9)
    assert isinstance(m, AdaptiveMutex)
    assert isinstance(m.inner, TicketMutex)   # Algorithm 3 never changes
    # measured signal drives the strategy; identical re-selections are
    # not counted as retunes
    assert m.retune(0.0) is WaitStrategy.SPIN
    assert m.retune(0.0) is WaitStrategy.SPIN
    assert m.retunes == 1
    assert m.retune(0.95) is WaitStrategy.SLEEP
    assert m.retunes == 2
    # the mutex still is a mutex, and its counters still count
    with m:
        pass
    assert m.acquires == 1 and m.contended_acquires == 0
    st = m.lock_stats()
    assert st["retunes"] == 2 and st["strategy"] == "sleep"
    # default retune() reads the inner lock's measured sliding window
    assert m.retune() is WaitStrategy.SPIN    # uncontended so far


def test_mutex_lock_stats_count_contention():
    import threading
    import time

    from repro.core.hostsync import TicketMutex
    m = TicketMutex()
    m.lock()
    t = threading.Thread(target=lambda: (m.lock(), m.unlock()))
    t.start()
    deadline = time.monotonic() + 5.0
    while m._ticket.load() < 2:               # waiter holds its ticket
        assert time.monotonic() < deadline
        time.sleep(1e-4)
    m.unlock()
    t.join()
    assert m.acquires == 2
    assert m.contended_acquires == 1          # the waiter's acquire
    assert m.held_s > 0.0
    assert 0.0 < m.recent_contention() <= 0.5
    m.reset_stats()
    assert m.acquires == 0 and m.recent_contention() == 0.0


# --------------------------------------------- batched-grant window op
def test_ticket_lock_batch_window_accounting():
    """The batched-grant plan: FIFO grant order identical to per-page
    granting, page offsets are the exclusive running total, and the
    atomics ledger says one FA per requester vs one per page."""
    from repro.kernels.ticket_lock.ops import (ticket_lock_batch_window,
                                               ticket_lock_window)
    arrival = np.asarray([0, 1, 2, 3, 4], np.int32)
    counts = np.asarray([3, 1, 0, 4, 2], np.int64)
    g, starts, total, (batched, per_page) = ticket_lock_batch_window(
        arrival, counts)
    gw, _, _ = ticket_lock_window(arrival)
    np.testing.assert_array_equal(g, np.asarray(gw))  # same FIFO grants
    np.testing.assert_array_equal(starts, [0, 3, 4, 4, 8])
    assert total == 10 and (batched, per_page) == (5, 10)
    # kernel and pure-jnp ref agree
    g2, s2, t2, a2 = ticket_lock_batch_window(arrival, counts,
                                              use_kernel=False)
    np.testing.assert_array_equal(g, g2)
    np.testing.assert_array_equal(starts, s2)
    assert (t2, a2) == (total, (batched, per_page))
    with pytest.raises(ValueError):
        ticket_lock_batch_window(arrival, counts[:3])
    with pytest.raises(ValueError):
        ticket_lock_batch_window(arrival, -counts)
