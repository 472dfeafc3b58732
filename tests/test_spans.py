"""Host spans and lane counters of the serving round (DESIGN.md §17).

The serving program names its host work on the profiler's trace
(``repro.serve.engine.SPANS``) and counts the round programs' lanes
(``lanes_dispatched`` / ``lanes_used`` in ``stats()``). These tests
drive a tiny paged engine with chunked prefill and the prefix cache
through the async front-end under ``jax.profiler.trace`` and read the
written ``.xplane.pb`` back with ``ProfileData``: every span fires, the
admission spans nest where the round puts them, ``serve.step`` carries
the round number, and tracing changes no token.
"""

import asyncio
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_arch
from repro.models import attention, build_model
from repro.serve import AsyncFrontend, SlotServeEngine
from repro.serve.engine import SPANS

NEW_TOKENS = 6


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_arch("qwen3-14b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def prompts_for(cfg):
    """Three prompts longer than a chunk (chunked prefill); the third
    repeats the first, so once the first retires it is served from the
    prefix cache."""
    rng = np.random.default_rng(11)
    p = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
         for n in (30, 13)]
    return p + [p[0].copy()]


def make_engine(model, params, capacity=2, chunk=6):
    return SlotServeEngine(model, params, capacity=capacity, max_len=48,
                           decode_chunk=2, seed=0, kv_layout="paged",
                           page_size=8, prefill_chunk_tokens=chunk,
                           prefix_sharing="on", prefix_cache="on")


def serve(engine, prompts):
    """Two prompts at once, the third after both have finished."""
    async def main():
        async with AsyncFrontend(engine) as fe:
            first = [await fe.submit(p, NEW_TOKENS) for p in prompts[:2]]
            outs = [await h.collect() for h in first]
            last = await fe.submit(prompts[2], NEW_TOKENS)
            outs.append(await last.collect())
            return outs
    return asyncio.run(main())


def host_events(trace_dir):
    """``(line, name, start_ns, end_ns, stats)`` of every host event
    (stats read for ``serve.step`` only)."""
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                out.append(((plane.name, i), e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns),
                            dict(e.stats) if e.name == "serve.step"
                            else {}))
    return out


@pytest.fixture(scope="module")
def traced(model_and_params, tmp_path_factory):
    cfg, model, params = model_and_params
    engine = make_engine(model, params)
    tdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(tdir):
        outs = serve(engine, prompts_for(cfg))
    return engine, outs, host_events(tdir)


def test_every_span_fires_and_nests(traced):
    engine, _, events = traced
    names = {n for _, n, *_ in events}
    assert set(SPANS) <= names, set(SPANS) - names
    # no program span outside the tuple
    prefixes = ("serve.", "frontend.")
    assert {n for n in names if n.startswith(prefixes)} == set(SPANS)
    # a prompt served from retained pages, and prompts in chunks
    assert engine.prefix_hits >= 1 and engine.prefill_chunks >= 3

    def inside(inner, outer):
        spans = [e for e in events if e[1] == outer]
        for line, _, s, t, _ in (e for e in events if e[1] == inner):
            assert any(line == o[0] and o[2] <= s and t <= o[3]
                       for o in spans), (inner, outer, s)

    inside("serve.grant", "serve.admit")
    for name in ("serve.admit", "serve.grow", "serve.dispatch",
                 "serve.wait", "serve.harvest", "serve.retire"):
        inside(name, "serve.step")


def test_one_step_span_per_round_numbered(traced):
    engine, _, events = traced
    steps = sorted((s, st) for _, n, s, _, st in events
                   if n == "serve.step")
    assert len(steps) == engine.rounds
    assert [int(st["step_num"]) for _, st in steps] == list(
        range(engine.rounds))
    # each round dispatches at most once: no more waits than steps
    assert sum(n == "serve.wait" for _, n, *_ in events) == \
        engine.decode_dispatches <= engine.rounds


def test_tokens_identical_with_profiler_off(model_and_params, traced):
    cfg, model, params = model_and_params
    _, traced_outs, _ = traced
    outs = serve(make_engine(model, params), prompts_for(cfg))
    assert outs == traced_outs
    assert all(len(o) == NEW_TOKENS for o in outs)


def test_lane_counters_by_hand(model_and_params):
    """Two prompts shorter than a chunk, served together: round 1 admits
    both and carries both chunks, round 2 decodes both to the end."""
    cfg, model, params = model_and_params
    rng = np.random.default_rng(3)
    engine = make_engine(model, params, capacity=2, chunk=8)
    for n in (5, 7):
        engine.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                      3)
    engine.run_until_done()
    assert engine.rounds == 2 and engine.prefill_chunks == 2
    st = engine.stats()
    # round 1: 2 bucket rows x (2 decode steps + 8 chunk lanes); round 2:
    # 2 rows x 2 decode steps
    assert st["lanes_dispatched"] == 2 * (2 + 8) + 2 * 2
    # round 1 keeps the prompts' 5 + 7 positions (the first tokens come
    # from the chunk's logits); round 2 appends the 2 x 2 decode tokens
    assert st["lanes_used"] == 5 + 7 + 2 * 2
    assert engine.decode_dispatches == 2


@pytest.mark.parametrize("path", ["gather", "fused", "chunk"])
def test_paged_attention_scope_in_op_metadata(path):
    """Both read paths, decode and chunk, lower under the scope name, so
    the device trace can attribute their ops whatever they are called."""
    cfg = get_arch("qwen3-14b").reduced()
    kv, hd, h = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_heads
    b, ps, n_pages, p_cap = 2, 8, 6, 3
    arena = jax.ShapeDtypeStruct((n_pages, ps, kv, hd), np.float32)
    pages = jax.ShapeDtypeStruct((b, p_cap), np.int32)
    if path == "chunk":
        def fn(q, k, v, tab, pos):
            return attention.paged_chunk_attention(
                None, cfg, q, k, v, tab, pos, window=None)
        q = jax.ShapeDtypeStruct((b, 4, h, hd), np.float32)
        last = jax.ShapeDtypeStruct((b, 4), np.int32)
    else:
        def fn(q, k, v, tab, lens):
            return attention.paged_decode_attention(
                None, cfg, q, k, v, tab, lens, window=None, impl=path)
        q = jax.ShapeDtypeStruct((b, 1, h, hd), np.float32)
        last = jax.ShapeDtypeStruct((b,), np.int32)
    text = jax.jit(fn).lower(q, arena, arena, pages, last).as_text(
        debug_info=True)
    assert f"/{attention.PAGED_ATTENTION_SCOPE}/" in text
