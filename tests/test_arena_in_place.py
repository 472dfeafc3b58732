"""The layer scan carries the stacked cache and updates it in place.

``LM.decode_step`` and ``LM.prefill_chunk`` run their layers under one
scan whose carry holds the ``periods`` cache: each layer writes its new
K/V (or recurrent state) into its own cells of the stacked leaves and
reads the layer back from there. Here they are held to a per-layer
reference loop written in this file: slice each layer's cache out, run
the block on the slice, write the slice back. Logits, tokens and the
whole cache must match bit for bit after several decode steps and a
prefill chunk, for the paged arena (with sentinel table entries and
writes past the block table, which drop), the contiguous layout, and a
mamba-attention hybrid with an unrolled leftover layer.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import blocks, build_model
from repro.models.layers import embed, rmsnorm

B, PS, NUM_PAGES, P_CAP, T = 4, 4, 24, 5, 20
DROP = 2 ** 30          # a write position past any table: the write drops


def _model(arch, extra_layers=0):
    cfg = get_arch(arch).reduced()
    cfg = dataclasses.replace(
        cfg, num_layers=2 * len(cfg.layer_pattern) + extra_layers)
    return cfg, build_model(cfg)


def _cache(model, layout, seed):
    """A random cache of the given layout: every leaf filled, so a write
    to a wrong cell or a lost write shows in the comparison."""
    rng = np.random.default_rng(seed)
    shapes = model.init_cache(B, T, for_shapes=True)

    def fill(path, s):
        shape = s.shape
        if layout == "paged" and path[-1].key in ("k", "v"):
            lead = shape[:-4]               # [n_periods] or ()
            shape = lead + (NUM_PAGES, PS) + shape[-2:]
        return jnp.asarray(rng.standard_normal(shape), s.dtype)

    cache = {"periods": jax.tree_util.tree_map_with_path(
                 fill, shapes["periods"]),
             "leftover": jax.tree_util.tree_map_with_path(
                 fill, shapes["leftover"])}
    if layout == "paged":
        # row 0: three real pages then sentinels; row 1: a sentinel hole
        # where its next token goes; row 2: a full table whose writes
        # run past it; row 3: every entry a sentinel (a paused row)
        pages = np.full((B, P_CAP), NUM_PAGES, np.int32)
        ids = rng.permutation(NUM_PAGES)
        pages[0, :3] = ids[:3]
        pages[1, :2] = ids[3:5]
        pages[2, :] = ids[5:5 + P_CAP]
        cache["pages"] = jnp.asarray(pages)
        cache["len"] = jnp.asarray([5, 8, P_CAP * PS - 2, 3], jnp.int32)
    else:
        cache["len"] = jnp.asarray([3, 7, T - 2, 0], jnp.int32)
    return cache


def _per_layer(model, params, cache, x, step):
    """The reference loop: each stacked layer sliced out, stepped on
    its own, written back."""
    periods = dict(cache["periods"])
    for i in range(model.n_periods):
        pp = jax.tree_util.tree_map(lambda a: a[i], params["periods"])
        for j, (kind, use_moe) in enumerate(model.layout):
            name = f"layer_{j}"
            lc = jax.tree_util.tree_map(lambda a: a[i], periods[name])
            x, nc = step(pp[name], x, lc, kind, use_moe)
            periods[name] = jax.tree_util.tree_map(
                lambda a, n: a.at[i].set(n), periods[name], nc)
    leftover = {}
    for j in range(len(model.leftover)):
        kind, use_moe = model.layout[j]
        name = f"layer_{j}"
        x, leftover[name] = step(params["leftover"][name], x,
                                 cache["leftover"][name], kind, use_moe)
    x = rmsnorm(params["final_norm"], x, model.cfg.norm_eps)
    out = dict(cache, periods=periods, leftover=leftover)
    return model._unembed(params, x), out


def _ref_decode(model, params, cache, tok, impl):
    x = embed(params["embed"], tok[:, None]).astype(model.cfg.dtype)

    def step(p, x, lc, kind, use_moe):
        return blocks.block_decode(p, x, lc, cache["len"], model.cfg, kind,
                                   use_moe, pages=cache.get("pages"),
                                   attn_impl=impl)

    logits, out = _per_layer(model, params, cache, x, step)
    out["len"] = cache["len"] + 1
    return logits[:, 0], out


def _ref_chunk(model, params, cache, tokens, positions, write_pos):
    x = embed(params["embed"], tokens).astype(model.cfg.dtype)

    def step(p, x, lc, kind, use_moe):
        return blocks.block_prefill_chunk(p, x, lc, model.cfg, kind,
                                          use_moe, positions, write_pos,
                                          pages=cache.get("pages"))

    return _per_layer(model, params, cache, x, step)


def _assert_same(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


CASES = [
    ("qwen3-14b", "paged", "gather", 0),
    ("qwen3-14b", "paged", "fused", 0),
    ("qwen3-14b", "contiguous", "gather", 1),
    ("jamba-1.5-large-398b", "paged", "gather", 1),
    ("jamba-1.5-large-398b", "contiguous", "gather", 0),
]


@pytest.mark.parametrize("arch,layout,impl,extra", CASES,
                         ids=["-".join(map(str, c)) for c in CASES])
def test_carried_cache_matches_per_layer_loop(arch, layout, impl, extra):
    cfg, model = _model(arch, extra)
    params = model.init(jax.random.PRNGKey(1))
    cache = _cache(model, layout, seed=2)
    got_c = ref_c = cache

    decode = jax.jit(lambda p, c, t: model.decode_step(p, c, t,
                                                       attn_impl=impl))
    ref = jax.jit(lambda p, c, t: _ref_decode(model, p, c, t, impl))
    tok = jnp.asarray([1, 2, 3, 4], jnp.int32)
    got_t = ref_t = tok
    for _ in range(3):
        got_l, got_c = decode(params, got_c, got_t)
        ref_l, ref_c = ref(params, ref_c, ref_t)
        np.testing.assert_array_equal(np.asarray(got_l), np.asarray(ref_l))
        got_t = jnp.argmax(got_l, -1).astype(jnp.int32)
        ref_t = jnp.argmax(ref_l, -1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(got_t), np.asarray(ref_t))
    _assert_same(got_c, ref_c)
    # the steps wrote: the carried cache is not the one it started from
    assert not all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
                   zip(jax.tree_util.tree_leaves(cache["periods"]),
                       jax.tree_util.tree_leaves(got_c["periods"])))
    if layout == "paged":
        # in every layer, exactly the cells of in-table writes changed:
        # row 0's three tokens and row 2's two before its table ends;
        # row 1's (a sentinel entry), row 2's third and row 3's dropped
        for name, lc in cache["periods"].items():
            if "k" not in lc:
                continue
            diff = np.asarray(lc["k"]) != np.asarray(
                got_c["periods"][name]["k"])
            changed = diff.any(axis=(-2, -1)).sum(axis=(1, 2))
            np.testing.assert_array_equal(changed, [5] * model.n_periods)

    if cfg.attention_free or any(k == "mamba" for k in cfg.layer_pattern):
        return                  # chunked prefill is attention-only
    c = 3
    lens = np.asarray(got_c["len"])
    positions = jnp.asarray(lens[:, None] + np.arange(c), jnp.int32)
    write_pos = np.asarray(positions).copy()
    write_pos[1, 1:] = DROP                         # a partial last chunk
    write_pos = jnp.asarray(write_pos)
    tokens = jnp.arange(B * c, dtype=jnp.int32).reshape(B, c) % 7 + 1
    chunk = jax.jit(model.prefill_chunk)
    ref_chunk = jax.jit(lambda p, cc, *a: _ref_chunk(model, p, cc, *a))
    got_l, got_c = chunk(params, got_c, tokens, positions, write_pos)
    ref_l, ref_c = ref_chunk(params, ref_c, tokens, positions, write_pos)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(ref_l))
    _assert_same(got_c, ref_c)
