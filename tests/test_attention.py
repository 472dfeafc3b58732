"""Attention paths vs the O(S^2) oracle + head-plan equivalences."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.models.attention import (attention_out, banded_attention,
                                    blocked_attention,
                                    cached_chunk_attention,
                                    cached_decode_attention,
                                    chunk_decode_attention, decode_attention,
                                    expand_kv, head_plan, kv_chunked_attention,
                                    kv_grouped, naive_reference_attention,
                                    paged_chunk_attention,
                                    paged_decode_attention)
from repro.sharding.act import activation_sharding


def _qkv(key, b, s, t, h, kv, hd):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, kv, hd), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("s,t,h,kv,hd,causal,window", [
    (64, 64, 4, 2, 16, True, None),
    (64, 64, 4, 1, 16, True, 24),
    (48, 96, 4, 4, 16, False, None),
    (128, 128, 8, 2, 32, True, None),
    (40, 40, 6, 3, 8, True, None),     # non-pow2
])
def test_blocked_attention_vs_oracle(s, t, h, kv, hd, causal, window):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, s, t, h, kv, hd)
    ke, ve = expand_kv(k, h), expand_kv(v, h)
    got = blocked_attention(q, ke, ve, causal=causal, window=window,
                            q_chunk=16, kv_chunk=16)
    want = naive_reference_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_kv_chunked_vs_oracle(causal):
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 64, 64, 4, 2, 16)
    ke, ve = expand_kv(k, 4), expand_kv(v, 4)
    got = kv_chunked_attention(q, ke, ve, causal=causal, kv_chunk=16)
    want = naive_reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("window", [8, 24, 64])
def test_banded_vs_oracle(window):
    q, k, v = _qkv(jax.random.PRNGKey(2), 2, 64, 64, 4, 4, 16)
    ke, ve = expand_kv(k, 4), expand_kv(v, 4)
    got = banded_attention(q, ke, ve, window=window)
    want = naive_reference_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_head_padding_is_inert():
    """Zero-padded q heads + masked wo == unpadded computation."""
    b, s, h, hd, d = 2, 32, 6, 8, 24
    key = jax.random.PRNGKey(3)
    q, k, v = _qkv(key, b, s, s, h, 3, hd)
    wo = jax.random.normal(jax.random.PRNGKey(4), (h, hd, d), jnp.float32)

    # unpadded
    y = blocked_attention(q, expand_kv(k, h), expand_kv(v, h),
                          causal=True, q_chunk=8, kv_chunk=8)
    out_ref = attention_out({"wo": wo}, y, h)

    # padded to 8 heads: extra q heads get random garbage, wo rows zeroed
    hp = 8
    q_pad = jnp.concatenate(
        [q, jax.random.normal(jax.random.PRNGKey(5), (b, s, hp - h, hd))],
        axis=2)
    wo_pad = jnp.concatenate(
        [wo, jax.random.normal(jax.random.PRNGKey(6), (hp - h, hd, d))],
        axis=0)
    y_pad = blocked_attention(q_pad, expand_kv(k, h, pad_to=hp),
                              expand_kv(v, h, pad_to=hp),
                              causal=True, q_chunk=8, kv_chunk=8)
    out_pad = attention_out({"wo": wo_pad}, y_pad, h)
    np.testing.assert_allclose(np.asarray(out_pad), np.asarray(out_ref),
                               atol=3e-5, rtol=3e-5)


def test_head_plan_decisions():
    assert head_plan(64, 16) == ("shard", 64)
    assert head_plan(40, 16) == ("pad", 48)
    assert head_plan(24, 16) == ("pad", 32)
    assert head_plan(12, 16) == ("pad", 16)
    assert head_plan(4, 16) == ("seq", 4)
    assert head_plan(40, 1) == ("shard", 40)  # no policy -> exact


def test_expand_kv_mapping():
    k = jnp.arange(2 * 4 * 2 * 3, dtype=jnp.float32).reshape(2, 4, 2, 3)
    ke = expand_kv(k, 6)  # 2 kv heads -> 6 q heads, groups of 3
    for h in range(6):
        np.testing.assert_array_equal(
            np.asarray(ke[:, :, h]), np.asarray(k[:, :, h // 3]))
    kep = expand_kv(k, 6, pad_to=8)
    assert kep.shape[2] == 8
    assert np.all(np.asarray(kep[:, :, 6:]) == 0)


# ------------------------------------------- cached reads: grouped form
# A bf16 cache rounds q, k, v, each score and each softmax weight at
# 2^-9 relative. With N(0, 1) data a score q.k/sqrt(hd) stays below
# about 6, so a score moves by at most ~0.02 and its weight by ~2%
# relative; the output, a convex combination of V rows with |v| < 5,
# then moves by well under 2^-5 (measured: under 0.01 at hd 128).
BF16_TOL = 2.0 ** -5
F32_TOL = 1e-5


def _cache_case(seed, *, b, c, t, kv, g, hd, dtype):
    """q [B,C,KV*G,hd] and an unexpanded k/v [B,T,KV,hd] cache."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, c, kv * g, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((b, t, kv, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((b, t, kv, hd)), dtype)
    return q, k, v


def _reference(q, k, v, qpos, window):
    """Oracle for queries q [B,C,H,hd] at absolute positions qpos [B,C]
    (distinct within a row) over caches k/v [B,T,KV,hd]: each query is
    placed at its position in a length-T sequence and the causal
    (windowed) naive_reference_attention read back there, in float32."""
    b, c = qpos.shape
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    rows = jnp.arange(b)[:, None]
    full = jnp.zeros((b, k.shape[1]) + q.shape[2:], jnp.float32)
    full = full.at[rows, qpos].set(f32(q))
    out = naive_reference_attention(full, f32(k), f32(v), causal=True,
                                    window=window)
    return out[rows, qpos]


def _tol(dtype):
    return F32_TOL if dtype == jnp.float32 else BF16_TOL


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 5])
@pytest.mark.parametrize("g", [1, 5, 8])
def test_decode_attention_grouped(g, window, dtype):
    """KV-head cache (grouped contraction) against the cache expanded
    to one head per query head and against the oracle, with ragged
    per-row lengths."""
    b, t, kv, hd = 3, 24, 2, 16
    q, k, v = _cache_case(10 + g, b=b, c=1, t=t, kv=kv, g=g, hd=hd,
                          dtype=dtype)
    lens = jnp.asarray([1, 13, t], jnp.int32)
    h = kv * g
    got = decode_attention(q, k, v, lens, window=window)
    exp = decode_attention(q, expand_kv(k, h), expand_kv(v, h), lens,
                           window=window)
    assert got.shape == (b, 1, h, hd) and got.dtype == q.dtype
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32),
                               atol=tol, rtol=tol)
    ref = _reference(q, k, v, lens[:, None] - 1, window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref), atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 5])
@pytest.mark.parametrize("g", [1, 5, 8])
def test_chunk_decode_attention_grouped(g, window, dtype):
    """Chunked prefill over a KV-head cache: rows at ragged offsets,
    each query attending its own causal (windowed) prefix."""
    b, c, t, kv, hd = 3, 4, 24, 2, 16
    q, k, v = _cache_case(20 + g, b=b, c=c, t=t, kv=kv, g=g, hd=hd,
                          dtype=dtype)
    qpos = jnp.asarray([0, 7, t - c])[:, None] + jnp.arange(c)[None, :]
    h = kv * g
    got = chunk_decode_attention(q, k, v, qpos, window=window)
    exp = chunk_decode_attention(q, expand_kv(k, h), expand_kv(v, h), qpos,
                                 window=window)
    assert got.shape == (b, c, h, hd) and got.dtype == q.dtype
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32),
                               atol=tol, rtol=tol)
    ref = _reference(q, k, v, qpos, window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref), atol=tol, rtol=tol)


def _paged_case(seed, *, b, kv, g, hd, ps, num_pages, p_cap):
    """An arena, a block table with sentinel (num_pages) tails past
    each row's live pages, and each row's cache in position order
    ([B, P*ps, KV, hd], zeros past its live pages)."""
    rng = np.random.default_rng(seed)
    k = rng.standard_normal((num_pages, ps, kv, hd)).astype(np.float32)
    v = rng.standard_normal((num_pages, ps, kv, hd)).astype(np.float32)
    lens = np.asarray([3, ps + 3, p_cap * ps - 2][:b])
    pages = np.full((b, p_cap), num_pages, np.int32)
    ids = rng.permutation(num_pages)
    rows_k = np.zeros((b, p_cap * ps, kv, hd), np.float32)
    rows_v = np.zeros_like(rows_k)
    at = 0
    for i in range(b):
        need = -(-int(lens[i]) // ps)
        pages[i, :need] = ids[at:at + need]
        at += need
        rows_k[i, :need * ps] = k[pages[i, :need]].reshape(-1, kv, hd)
        rows_v[i, :need * ps] = v[pages[i, :need]].reshape(-1, kv, hd)
    return (jnp.asarray(k), jnp.asarray(v), jnp.asarray(pages),
            jnp.asarray(lens, jnp.int32), jnp.asarray(rows_k),
            jnp.asarray(rows_v))


@pytest.mark.parametrize("window", [None, 5])
@pytest.mark.parametrize("g", [1, 5, 8])
def test_paged_gather_reads_grouped(g, window):
    """The paged gather path (decode and chunk) over a block table with
    sentinel entries, on one device, against the oracle on each row's
    live pages."""
    b, kv, hd, ps, num_pages, p_cap, c = 3, 2, 16, 4, 16, 4, 3
    h = kv * g
    cfg = types.SimpleNamespace(num_heads=h)
    k, v, pages, lens, rows_k, rows_v = _paged_case(
        30 + g, b=b, kv=kv, g=g, hd=hd, ps=ps, num_pages=num_pages,
        p_cap=p_cap)
    rng = np.random.default_rng(40 + g)
    q = jnp.asarray(rng.standard_normal((b, c, h, hd)), jnp.float32)
    assert kv_grouped(h, kv, h)

    got = paged_decode_attention(None, cfg, q[:, :1], k, v, pages, lens,
                                 window=window, impl="gather")
    ref = _reference(q[:, :1], rows_k, rows_v, lens[:, None] - 1, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=F32_TOL, rtol=F32_TOL)

    # the chunk's queries are the last c positions of each row's cache
    qpos = lens[:, None] - c + jnp.arange(c)[None, :]
    got = paged_chunk_attention(None, cfg, q, k, v, pages, qpos,
                                window=window)
    ref = _reference(q, rows_k, rows_v, qpos, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=F32_TOL, rtol=F32_TOL)


def _broadcasts_to(shape, fn, *args):
    """Whether tracing ``fn`` builds a broadcast of the given shape."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return any(e.primitive.name == "broadcast_in_dim"
               and e.outvars[0].aval.shape == shape
               for e in jaxpr.jaxpr.eqns)


@pytest.mark.parametrize("h,kv,tp,grouped", [
    (40, 8, 1, True),      # one device: always grouped
    (40, 8, 16, False),    # 'pad' plan: 40 query heads padded to 48
    (32, 2, 4, False),     # 'shard' plan, but fewer kv heads than tp
    (32, 8, 4, True),      # 'shard' plan, kv heads split evenly
])
def test_cached_read_form_follows_head_plan(h, kv, tp, grouped):
    """Under a tp-way activation policy the cached reads keep
    ``expand_kv`` wherever the head sharding needs it, and contract
    against the KV heads everywhere else."""
    mesh = AbstractMesh((tp,), ("model",))
    b, t, c, hd = 2, 8, 2, 4
    with activation_sharding(mesh, ()):
        hq = head_plan(h)[1]
        assert kv_grouped(h, kv, hq) == grouped
        cfg = types.SimpleNamespace(num_heads=h)
        k = jnp.zeros((b, t, kv, hd), jnp.float32)
        lens = jnp.full((b,), t, jnp.int32)
        qpos = jnp.zeros((b, c), jnp.int32)

        def dec(q, k, v, lens):
            return cached_decode_attention(None, cfg, q, k, v, lens,
                                           window=None)

        def chunk(q, k, v, qpos):
            return cached_chunk_attention(None, cfg, q, k, v, qpos,
                                          window=None)

        expanded = (b, t, kv, h // kv, hd)       # expand_kv's broadcast
        q1 = jnp.zeros((b, 1, hq, hd), jnp.float32)
        qc = jnp.zeros((b, c, hq, hd), jnp.float32)
        assert _broadcasts_to(expanded, dec, q1, k, k, lens) != grouped
        assert _broadcasts_to(expanded, chunk, qc, k, k, qpos) != grouped
