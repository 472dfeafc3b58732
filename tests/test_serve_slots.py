"""Slot-pool serving: arena correctness, engine round-trips, and
host-semaphore vs Algorithm-5-kernel admission equivalence."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # hypothesis is optional in this image (tests/_hypothesis_compat.py)
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, st

from repro.configs import get_arch
from repro.core.hostsync import SleepingSemaphore
from repro.kernels.semaphore.ops import (semaphore_admission,
                                         semaphore_admission_window)
from repro.models import build_model
from repro.serve.engine import ServeEngine, SlotServeEngine
from repro.serve.kv_slots import SlotPool


@pytest.fixture(scope="module")
def lm_setup():
    cfg = get_arch("qwen3-14b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ----------------------------------------------------------------- slot pool
def test_slot_pool_insert_evict_roundtrip(lm_setup):
    cfg, model, params = lm_setup
    pool = SlotPool(model, capacity=3, max_len=24)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                 cfg.vocab_size)
    _, c0 = model.prefill(params, {"tokens": prompts[0:1]}, max_len=24)
    _, c1 = model.prefill(params, {"tokens": prompts[1:2]}, max_len=24)

    s0 = pool.acquire(rid=10)
    s1 = pool.acquire(rid=11)
    assert (s0, s1) == (0, 1)       # FIFO slot reuse order
    pool.insert(s0, c0, 6)
    pool.insert(s1, c1, 6)
    assert pool.n_free == 1 and pool.n_active == 2
    np.testing.assert_array_equal(np.asarray(pool.lens), [6, 6, 0])

    # arena row s0 holds c0's KV: compare one periods leaf
    arena_k = np.asarray(
        pool.arena["periods"]["layer_0"]["k"])       # [NP, K, S, KV, hd]
    want_k = np.asarray(c0["periods"]["layer_0"]["k"])  # [NP, 1, S, KV, hd]
    np.testing.assert_allclose(arena_k[:, s0:s0 + 1, :6], want_k[:, :, :6],
                               rtol=1e-5, atol=1e-5)

    pool.evict(s0)
    assert pool.n_free == 2
    s2 = pool.acquire(rid=12)
    assert s2 == 2                  # FIFO: slot 2 reused before slot 0
    with pytest.raises(RuntimeError):
        pool.evict(s0)              # double-evict is an error


def test_slot_pool_encdec_batch_axes():
    cfg = get_arch("whisper-small").reduced()
    model = build_model(cfg)
    pool = SlotPool(model, capacity=2, max_len=8)
    # every leaf carries the capacity on its detected batch axis
    for leaf in jax.tree_util.tree_leaves(pool.arena):
        assert 2 in leaf.shape


# -------------------------------------------------------------- slot engine
def test_slot_engine_n_gt_k_roundtrip(lm_setup):
    cfg, model, params = lm_setup
    eng = SlotServeEngine(model, params, capacity=3, max_len=48,
                          decode_chunk=2)
    rng = np.random.default_rng(0)
    for _ in range(7):
        eng.submit(rng.integers(0, cfg.vocab_size, 10), max_new_tokens=5)
    for _ in range(100):
        if not (eng.queue or eng.active):
            break
        # each round admits the FIFO prefix the free slots hold
        want = min(eng.capacity - len(eng.active), len(eng.queue))
        granted = len(eng.grant_log)
        eng.step()
        assert len(eng.grant_log) - granted == want
    assert len(eng.finished) == 7
    assert eng.grant_log == sorted(eng.grant_log)          # FIFO grants
    assert all(len(r.out_tokens) == 5 for r in eng.finished)
    assert eng.admission.in_flight == 0                    # sem drained
    st_ = eng.stats()
    assert st_["p99_wait_steps"] >= st_["p50_wait_steps"] >= 0


def test_slot_engine_matches_legacy_greedy(lm_setup):
    """Batched slot decode must be token-identical to the legacy
    per-request loop under greedy sampling (same params, same prompts)."""
    cfg, model, params = lm_setup
    eng = SlotServeEngine(model, params, capacity=2, max_len=32)
    legacy = ServeEngine(model, params, max_len=32)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, 7) for _ in range(3)]
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    eng.run_until_done(max_rounds=50)
    for req in sorted(eng.finished, key=lambda r: r.rid):
        out = legacy.generate(
            {"tokens": jnp.asarray(req.prompt)[None, :]}, 4)
        assert req.out_tokens == np.asarray(out.tokens)[0].tolist()


def test_slot_engine_eos_frees_slot_early(lm_setup):
    cfg, model, params = lm_setup
    eng = SlotServeEngine(model, params, capacity=1, max_len=32,
                          eos_id=0, decode_chunk=1)
    rng = np.random.default_rng(2)
    for _ in range(2):
        eng.submit(rng.integers(1, cfg.vocab_size, 6), max_new_tokens=12)
    eng.run_until_done(max_rounds=60)
    assert len(eng.finished) == 2
    for r in eng.finished:
        if r.eos:
            assert r.out_tokens[-1] == 0
            assert len(r.out_tokens) <= 12
        else:
            assert len(r.out_tokens) == 12


def test_slot_engine_rejects_oversized_prompt(lm_setup):
    cfg, model, params = lm_setup
    eng = SlotServeEngine(model, params, capacity=1, max_len=16)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(14, np.int32), max_new_tokens=4)


# ------------------------------------------------- model-level vector lens
def test_decode_step_vector_lens_match_scalar(lm_setup):
    """One batched decode over rows at different depths == two scalar-len
    decodes run separately (the refactor that lets slots share a step)."""
    cfg, model, params = lm_setup
    max_len = 16
    pa = jax.random.randint(jax.random.PRNGKey(3), (1, 5), 0, cfg.vocab_size)
    pb = jax.random.randint(jax.random.PRNGKey(4), (1, 9), 0, cfg.vocab_size)
    la, ca = model.prefill(params, {"tokens": pa}, max_len=max_len)
    lb, cb = model.prefill(params, {"tokens": pb}, max_len=max_len)

    pool = SlotPool(model, capacity=2, max_len=max_len)
    pool.insert(pool.acquire(0), ca, 5)
    pool.insert(pool.acquire(1), cb, 9)
    tok = jnp.asarray([int(jnp.argmax(la[0])), int(jnp.argmax(lb[0]))],
                      jnp.int32)
    logits_vec, cache_vec = model.decode_step(params, pool.cache_view(), tok)

    la2, _ = model.decode_step(params, ca, tok[0:1])
    lb2, _ = model.decode_step(params, cb, tok[1:2])
    np.testing.assert_allclose(np.asarray(logits_vec[0]), np.asarray(la2[0]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(logits_vec[1]), np.asarray(lb2[0]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(cache_vec["len"]), [6, 10])


def test_prefill_padded_length_matches_exact(lm_setup):
    """Right-padded prefill with a length vector == exact-length prefill."""
    cfg, model, params = lm_setup
    p = jax.random.randint(jax.random.PRNGKey(5), (1, 6), 0, cfg.vocab_size)
    exact_logits, _ = model.prefill(params, {"tokens": p}, max_len=16)
    padded = jnp.pad(p, ((0, 0), (0, 6)))
    pad_logits, cache = model.prefill(
        params, {"tokens": padded}, max_len=16,
        length=jnp.asarray([6], jnp.int32))
    np.testing.assert_allclose(np.asarray(pad_logits), np.asarray(exact_logits),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(cache["len"]), [6])


# ------------------------------------- host semaphore vs kernel timeline
def _host_semaphore_trace(n, capacity, completion_rng):
    """Run n FIFO arrivals through the real SleepingSemaphore with the
    main thread driving completions one at a time (in a random granted
    order), so each post() produces exactly one deterministic handoff.

    Arrival order is enforced by watching the semaphore's own count word
    (no posts happen during the spawn window — completions are gated on
    events the main thread sets afterwards). Returns (grant_order,
    max_occupancy)."""
    sem = SleepingSemaphore(capacity)
    lock = threading.Lock()
    order = []
    gauge = {"now": 0, "max": 0}
    release = [threading.Event() for _ in range(n)]

    def worker(i):
        sem.wait()
        with lock:
            order.append(i)
            gauge["now"] += 1
            gauge["max"] = max(gauge["max"], gauge["now"])
        release[i].wait(timeout=10.0)
        with lock:
            gauge["now"] -= 1
        sem.post()

    def grants():
        with lock:
            return len(order)

    def wait_until(pred):
        deadline = time.monotonic() + 5.0
        while not pred():
            assert time.monotonic() < deadline, "host trace timed out"
            time.sleep(1e-4)

    threads = []
    for i in range(n):
        t = threading.Thread(target=worker, args=(i,))
        t.start()
        threads.append(t)
        # count increments exactly once per wait() entry; no posts yet
        wait_until(lambda: sem._count.load() >= i + 1)
    wait_until(lambda: grants() >= min(capacity, n))

    done = set()
    while len(done) < n:
        with lock:
            candidates = [i for i in order if i not in done]
        nxt = candidates[completion_rng.integers(len(candidates))]
        expect = min(n, grants() + 1)           # one handoff per post
        release[nxt].set()
        done.add(nxt)
        wait_until(lambda: grants() >= expect)
    for t in threads:
        t.join()
    return order, gauge["max"]


@settings(max_examples=5, deadline=None)
@given(n=st.integers(6, 16), cap=st.integers(1, 4), held=st.integers(0, 4),
       seed=st.integers(0, 10_000))
def test_admission_equivalence_host_vs_kernel(n, cap, held, seed):
    """Property: the real Algorithm-5 host semaphore and the Pallas
    admission kernel agree on a FIFO arrival trace — same waited set,
    FIFO handoff order among waiters, occupancy <= K — even when holds
    complete out of order. With ``held`` requests in flight at t=0, the
    queue behind them enters without waiting exactly for its first
    ``min(cap - held, n)`` requests: the engine's admission rule
    ``min(n_free, n_queued)``."""
    held = min(held, cap)
    total = held + n
    rng = np.random.default_rng(seed)
    holds = rng.integers(1, 4, total).astype(np.float32)
    # kernel timeline: the holders at t=0, then the queue strictly
    # increasing behind them, gaps tiny vs holds (every hold >= 1)
    arrivals = np.concatenate([
        np.zeros(held, np.float32),
        np.arange(1, n + 1, dtype=np.float32) * 1e-3])
    g, r, waited = semaphore_admission_window(
        arrivals, holds, capacity=cap, window=32)
    # under-capacity prefix enters immediately; the rest queue
    assert list(waited) == [0] * min(cap, total) + [1] * max(total - cap, 0)
    k_q = min(cap - held, n)
    assert list(waited[held:]) == [0] * k_q + [1] * (n - k_q)
    assert np.all(np.diff(g) >= -1e-5)          # FIFO: grants monotone
    for i in range(total):                      # occupancy bound
        assert np.sum((g <= g[i] + 1e-6) & (r > g[i] + 1e-6)) <= cap

    order, max_occ = _host_semaphore_trace(total, cap, rng)
    assert max_occ <= cap
    # the non-waited set is the first `cap` arrivals (granted in any
    # interleaving); every ticketed waiter is handed off FIFO — exactly
    # the kernel's deterministic grant order
    k = min(cap, total)
    assert sorted(order[:k]) == list(range(k))
    assert order[k:] == list(range(k, total))


def test_admission_window_matches_unpadded():
    arr = np.asarray([0.0, 0.5, 0.6, 2.0], np.float32)
    hold = np.asarray([1.0, 3.0, 0.5, 1.0], np.float32)
    gw, rw, ww = semaphore_admission_window(arr, hold, capacity=2, window=16)
    g, r, w = semaphore_admission(jnp.asarray(arr), jnp.asarray(hold),
                                  capacity=2)
    np.testing.assert_allclose(gw, np.asarray(g), rtol=1e-6)
    np.testing.assert_allclose(rw, np.asarray(r), rtol=1e-6)
    np.testing.assert_array_equal(ww, np.asarray(w))


def test_admission_window_overflow_buckets_up():
    """A burst longer than the window buckets to the next power-of-2
    window (it used to raise ValueError on the serve hot loop) and still
    matches the unpadded timeline."""
    arr = np.sort(np.random.default_rng(3).uniform(0, 4, 21)
                  ).astype(np.float32)
    hold = np.random.default_rng(4).uniform(1, 2, 21).astype(np.float32)
    gw, rw, ww = semaphore_admission_window(arr, hold, capacity=3,
                                            window=16)
    assert gw.shape == (21,)
    g, r, w = semaphore_admission(jnp.asarray(arr), jnp.asarray(hold),
                                  capacity=3)
    np.testing.assert_allclose(gw, np.asarray(g), rtol=1e-6)
    np.testing.assert_allclose(rw, np.asarray(r), rtol=1e-6)
    np.testing.assert_array_equal(ww, np.asarray(w))


@pytest.mark.parametrize("layout,chunk", [("slots", None), ("slots", 4),
                                          ("paged", None), ("paged", 4)])
def test_round_program_takes_the_arena_over(lm_setup, layout, chunk):
    """Each of the four round programs (unbucketed or bucketed, with or
    without a prefill chunk) is handed the pool's cache and donated it:
    after ``step`` the arena the view shared is deleted, the pool holds
    the round's arena, and ``arena_in_place`` reads 1.0."""
    cfg, model, params = lm_setup
    kw = {"page_size": 4} if layout == "paged" else {}
    eng = SlotServeEngine(model, params, capacity=2, max_len=48,
                          decode_chunk=2, kv_layout=layout,
                          prefill_chunk_tokens=chunk, **kw)
    views = []
    view_of = eng.pool.cache_view
    eng.pool.cache_view = lambda: views.append(view_of()) or views[-1]
    rng = np.random.default_rng(0)
    for _ in range(3):
        eng.submit(rng.integers(1, cfg.vocab_size, 6), max_new_tokens=5)
    while eng.queue or eng.active:
        n = len(views)
        eng.step()
        assert len(views) == n + 1            # one dispatch a round
        arena = {k: v for k, v in views[-1].items()
                 if k not in ("len", "pages")}
        assert all(x.is_deleted() for x in jax.tree_util.tree_leaves(arena))
        assert not any(x.is_deleted()
                       for x in jax.tree_util.tree_leaves(eng.pool.arena))
    st = eng.stats()
    assert st["decode_dispatches"] == len(views) > 0
    assert st["arena_in_place"] == 1.0
    assert st["finished"] == 3
