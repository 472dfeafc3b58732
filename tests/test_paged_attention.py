"""Kernel-equivalence tier for the fused paged-decode path (DESIGN.md §16).

Three differential layers, all on the Pallas interpret tier (CPU):

  * **kernel vs oracle** — ``fused_paged_decode`` against the
    self-contained pure-jnp ``paged_decode_ref`` across page sizes,
    GQA ratios, ragged last-page lengths, sentinel-masked rows,
    sliding windows, and CoW-shared (duplicate) page ids. Logits
    within 1e-5.
  * **fused vs gather at the model layer** —
    ``models.attention.paged_decode_attention(impl="fused")`` against
    ``impl="gather"`` on exactly the shapes ``block_decode`` passes.
  * **engine streams** — two ``SlotServeEngine``s over the same fuzz
    trace, ``attention_impl`` fused vs gather: greedy token streams
    bit-identical, including prefix-shared/CoW traffic.

Plus the bucketed-dispatch retrace property (§16): a seeded
occupancy-churn trace through ``DecodeDispatchCache``-bucketed rounds
compiles a bounded bucket set and never retraces after warmup.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # hypothesis is optional in this image (tests/_hypothesis_compat.py)
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, st

from repro.configs import get_arch
from repro.kernels.paged_attention import (fused_paged_decode,
                                           paged_decode_fused,
                                           paged_decode_ref, row_live)
from repro.serve.dispatch import DecodeDispatchCache
from repro.models import build_model
from repro.models import attention as attn
from repro.serve.engine import SlotServeEngine
from repro.serve.fuzz import drive_trace, gen_trace

TOL = 1e-5


def _case(seed, *, b, kv, g, hd, ps, num_pages, p_cap, shared=False,
          dead_row=False):
    """Build a random paged-decode instance. Rows get ragged lengths
    (including a zero-length row when b > 2), allocated-prefix tables
    with sentinel tails, optionally duplicate (CoW-shared) page ids,
    and optionally one fully-sentinel (paused/masked) row."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, kv, g, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((num_pages, ps, kv, hd)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((num_pages, ps, kv, hd)),
                    jnp.float32)
    lens = rng.integers(1, p_cap * ps + 1, size=b)
    if b > 2:
        lens[1] = 0                       # freshly-admitted row
    pages = np.full((b, p_cap), num_pages, np.int32)   # sentinel tail
    for i in range(b):
        need = -(-int(lens[i]) // ps) if lens[i] else 0
        if shared and i > 0:
            # adopt row 0's prefix read-only (CoW sharing): identical
            # page ids must read identically from both paths
            prev = pages[0][pages[0] < num_pages]
            take = min(need, prev.size)
            pages[i, :take] = prev[:take]
            if need > take:
                pages[i, take:need] = rng.choice(
                    num_pages, size=need - take, replace=False)
        elif need:
            pages[i, :need] = rng.choice(num_pages, size=need,
                                         replace=False)
    if dead_row:
        pages[-1] = num_pages             # fully masked (paused) row
    return q, k, v, jnp.asarray(pages), jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("ps", [1, 4, 16])
@pytest.mark.parametrize("kv,g", [(8, 1), (2, 4), (1, 8)])  # H=8 GQA grid
def test_fused_matches_ref_across_pages_and_gqa(ps, kv, g):
    q, k, v, pages, lens = _case(
        ps * 10 + kv, b=4, kv=kv, g=g, hd=16, ps=ps,
        num_pages=24, p_cap=5, dead_row=True)
    got = fused_paged_decode(q, k, v, pages, lens, interpret=True)
    want = paged_decode_ref(q, k, v, pages, lens)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    # the fully-sentinel row must emit exact zeros from the kernel
    assert not bool(row_live(pages, 24)[-1])
    assert np.all(np.asarray(got[-1]) == 0.0)


@pytest.mark.parametrize("window", [2, 5])
def test_fused_matches_ref_sliding_window(window):
    q, k, v, pages, lens = _case(7, b=3, kv=2, g=2, hd=8, ps=4,
                                 num_pages=16, p_cap=4)
    got = fused_paged_decode(q, k, v, pages, lens, window=window,
                             interpret=True)
    want = paged_decode_ref(q, k, v, pages, lens, window=window)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_fused_matches_ref_cow_shared_pages():
    """Rows adopting another row's pages (prefix sharing / CoW) read
    the shared pages identically under both derivations."""
    q, k, v, pages, lens = _case(11, b=4, kv=2, g=4, hd=8, ps=4,
                                 num_pages=12, p_cap=4, shared=True)
    assert len(np.unique(np.asarray(pages))) < pages.size  # actually shared
    got = fused_paged_decode(q, k, v, pages, lens, interpret=True)
    want = paged_decode_ref(q, k, v, pages, lens)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_fused_ragged_last_page_lengths():
    """Every possible last-page occupancy 1..ps attends exactly the
    right prefix of the last page."""
    ps, p_cap = 4, 3
    for last in range(1, ps + 1):
        q, k, v, pages, _ = _case(100 + last, b=2, kv=1, g=2, hd=8,
                                  ps=ps, num_pages=8, p_cap=p_cap)
        lens = jnp.asarray([ps + last, 2 * ps + last], jnp.int32)
        got = fused_paged_decode(q, k, v, pages, lens, interpret=True)
        want = paged_decode_ref(q, k, v, pages, lens)
        np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1),
       ps=st.sampled_from([1, 2, 4, 8]),
       kv=st.sampled_from([1, 2, 4]),
       g=st.sampled_from([1, 2, 4]),
       shared=st.booleans())
def test_fused_matches_ref_property(seed, ps, kv, g, shared):
    q, k, v, pages, lens = _case(seed, b=3, kv=kv, g=g, hd=8, ps=ps,
                                 num_pages=16, p_cap=4, shared=shared)
    got = fused_paged_decode(q, k, v, pages, lens, interpret=True)
    want = paged_decode_ref(q, k, v, pages, lens)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


# ==================================================== model-layer parity
def test_model_layer_fused_vs_gather():
    """The production entry point: both impls of
    ``paged_decode_attention`` on the decode shapes block_decode passes
    ([B,1,H,hd] queries, fully-allocated live rows — the gather path's
    clipping semantics only match on rows the engine actually reads)."""
    rng = np.random.default_rng(3)
    b, h, kv, hd, ps, num_pages, p_cap = 3, 8, 2, 16, 4, 24, 4
    cfg = types.SimpleNamespace(num_heads=h)
    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((num_pages, ps, kv, hd)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((num_pages, ps, kv, hd)),
                    jnp.float32)
    pages = jnp.asarray(
        rng.choice(num_pages, size=(b, p_cap), replace=False).reshape(
            b, p_cap), jnp.int32)
    lens = jnp.asarray(rng.integers(1, p_cap * ps + 1, size=b), jnp.int32)
    for window in (None, 3):
        ref = attn.paged_decode_attention(None, cfg, q, k, v, pages, lens,
                                          window=window, impl="gather")
        got = attn.paged_decode_attention(None, cfg, q, k, v, pages, lens,
                                          window=window, impl="fused")
        assert got.shape == ref.shape and got.dtype == ref.dtype
        np.testing.assert_allclose(got, ref, atol=TOL, rtol=TOL)


def test_model_layer_rejects_unknown_impl():
    cfg = types.SimpleNamespace(num_heads=4)
    with pytest.raises(ValueError, match="unknown paged decode impl"):
        attn.paged_decode_attention(
            None, cfg, jnp.zeros((1, 1, 4, 8)), jnp.zeros((2, 2, 1, 8)),
            jnp.zeros((2, 2, 1, 8)), jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1,), jnp.int32), window=None, impl="flash")


def test_head_padded_queries_zero_pad_rows():
    """Under a 'pad' head plan the wrapper drops pad heads before the
    kernel and re-pads zeros after — matching what wo-masking makes the
    gather path produce."""
    q, k, v, pages, lens = _case(5, b=2, kv=2, g=2, hd=8, ps=4,
                                 num_pages=8, p_cap=2)
    qp = jnp.pad(q.reshape(2, 1, 4, 8), ((0, 0), (0, 0), (0, 2), (0, 0)))
    out = paged_decode_fused(qp, k, v, pages, lens, 4, interpret=True)
    assert out.shape == (2, 1, 6, 8)
    assert np.all(np.asarray(out[:, :, 4:]) == 0.0)
    np.testing.assert_allclose(
        out[:, :, :4].reshape(2, 2, 2, 8),
        paged_decode_ref(q, k, v, pages, lens), atol=TOL, rtol=TOL)


# ======================================================== engine streams
@pytest.fixture(scope="module")
def lm_setup():
    cfg = get_arch("qwen3-14b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _drive(model, params, seed, vocab, *, impl, bucketed="auto",
           sharing="auto", **kw):
    events = gen_trace(seed, n_requests=6, vocab=vocab, max_prompt=12,
                       max_new=6, p_shared=0.6, p_multi_turn=0.3,
                       p_cancel=0.1)
    eng = SlotServeEngine(model, params, capacity=3, max_len=128,
                          kv_layout="paged", page_size=4, seed=0,
                          prefill_chunk_tokens=4, decode_chunk=2,
                          attention_impl=impl, bucketed_dispatch=bucketed,
                          prefix_sharing=sharing, **kw)
    out = drive_trace(eng, events)
    eng.pool.check()
    assert eng.pool.pages.in_use == 0
    return out, eng


def test_engine_streams_bit_identical_fused_vs_gather(lm_setup):
    """The serving contract: same trace, same greedy streams, token for
    token, whichever read path decodes it — with prefix sharing on so
    CoW-shared pages are in play."""
    cfg, model, params = lm_setup
    for seed in (0, 3):
        got, eng_f = _drive(model, params, seed, cfg.vocab_size,
                            impl="fused")
        ref, _ = _drive(model, params, seed, cfg.vocab_size,
                        impl="gather")
        assert eng_f.stats()["attention_fused"] == 1.0
        assert got.keys() == ref.keys()
        for rid in ref:
            assert np.array_equal(ref[rid]["prompt"], got[rid]["prompt"])
            assert ref[rid]["out"] == got[rid]["out"], f"rid {rid}"


def test_engine_fused_without_bucketing(lm_setup):
    """attention_impl and bucketed_dispatch are independent axes: fused
    at full-batch dispatch matches gather too."""
    cfg, model, params = lm_setup
    got, eng = _drive(model, params, 1, cfg.vocab_size, impl="fused",
                      bucketed="off")
    ref, _ = _drive(model, params, 1, cfg.vocab_size, impl="gather",
                    bucketed="off")
    assert eng.stats()["bucketed_dispatch"] == 0.0
    assert eng.stats()["dispatch_traces"] == 0.0
    for rid in ref:
        assert ref[rid]["out"] == got[rid]["out"]


def test_engine_reports_grouped_kv_read(lm_setup):
    """On one device the round program's gather reads contract against
    the KV heads, and the engine's counter says so once a round has
    been traced."""
    cfg, _, _ = lm_setup
    cfg = dataclasses.replace(cfg, num_kv_heads=cfg.num_heads // 2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    _, eng = _drive(model, params, 2, cfg.vocab_size, impl="gather")
    assert eng.stats()["attention_kv_grouped"] == 1.0


def test_engine_ctor_validation(lm_setup):
    cfg, model, params = lm_setup
    with pytest.raises(ValueError, match="requires.*paged"):
        SlotServeEngine(model, params, capacity=2, max_len=64,
                        kv_layout="slots", attention_impl="fused")
    with pytest.raises(ValueError, match="unknown attention_impl"):
        SlotServeEngine(model, params, capacity=2, max_len=64,
                        kv_layout="paged", attention_impl="flash")
    with pytest.raises(ValueError, match="bucketed_dispatch='on'"):
        SlotServeEngine(model, params, capacity=2, max_len=64,
                        kv_layout="slots", bucketed_dispatch="on")
    # sampling engines silently fall back to full-batch dispatch
    eng = SlotServeEngine(model, params, capacity=2, max_len=64,
                          kv_layout="paged", temperature=0.7)
    assert not eng.bucketed_dispatch


# ============================================== retrace-count property
def _bounded_keys(eng):
    """The §16 bound: one trace key per (bucket, steps) — chunked
    rounds add the chunk ∈ {0, C} axis."""
    sizes = eng._dispatch_cache.bucket_sizes()
    return len(sizes) * 2      # chunk ∈ {0, C} variants


@pytest.mark.parametrize("impl", ["gather", "fused"])
def test_dispatch_never_retraces_under_occupancy_churn(lm_setup, impl):
    """Satellite 2: a seeded occupancy-churn trace (arrivals, EOS,
    cancellations) through the bucketed dispatch. The jit cache must
    never grow after warmup: zero retraces, and the traced-key set
    bounded by bucket_sizes × chunk variants. A second trace over the
    SAME engine must add no new traces beyond its own distinct keys."""
    cfg, model, params = lm_setup
    events = gen_trace(7, n_requests=8, vocab=cfg.vocab_size,
                       max_prompt=10, max_new=6, p_cancel=0.2,
                       arrival_spread=6)
    eng = SlotServeEngine(model, params, capacity=4, max_len=128,
                          kv_layout="paged", page_size=4, seed=0,
                          prefill_chunk_tokens=4, decode_chunk=2,
                          attention_impl=impl, bucketed_dispatch="on")
    drive_trace(eng, events)
    st_ = eng.stats()
    assert st_["dispatch_retraces"] == 0.0
    assert st_["dispatch_traces"] == st_["dispatch_trace_keys"]
    assert st_["dispatch_trace_keys"] <= _bounded_keys(eng)
    # warm now: replaying a fresh trace must hit only cached entries
    warm = eng._dispatch_cache.traces
    keys = set(eng._dispatch_cache.trace_keys)
    drive_trace(eng, gen_trace(8, n_requests=8, vocab=cfg.vocab_size,
                               max_prompt=10, max_new=6, p_cancel=0.2,
                               arrival_spread=6))
    new_keys = eng._dispatch_cache.trace_keys - keys
    assert eng._dispatch_cache.traces - warm == len(new_keys)
    assert eng._dispatch_cache.retraces == 0


def test_dispatch_cache_bucket_policy():
    """Unit shape of the bucket policy: pow-2 growth from 1, capped at
    capacity, and pad_rows fills with the out-of-range sentinel."""
    c = DecodeDispatchCache(12)
    assert [c.bucket(n) for n in (0, 1, 2, 3, 5, 8, 9, 12)] == \
        [1, 1, 2, 4, 8, 8, 12, 12]
    assert c.bucket_sizes() == [1, 2, 4, 8, 12]
    rows = c.pad_rows([3, 7], 4)
    assert rows.tolist() == [3, 7, 12, 12] and rows.dtype == np.int32
    c.record_trace((4, 2, 0))
    c.record_trace((4, 2, 0))
    assert c.traces == 2 and c.retraces == 1
