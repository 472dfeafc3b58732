"""Transformer/Mamba block wiring: pre-norm mixer + pre-norm FFN/MoE.

A *period* is one repetition of ``cfg.layer_pattern`` (e.g. jamba's
[attn, mamba x7]); the LM scans over stacked periods so HLO size is O(1)
in depth. Within a period, layers are unrolled (they are heterogeneous).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn
from . import mamba as mb
from . import moe as moe_mod
from .layers import mlp, mlp_spec, rmsnorm, rmsnorm_spec


def block_spec(cfg, kind: str, use_moe: bool, dtype):
    p = {"ln1": rmsnorm_spec(cfg.d_model)}
    if kind == "mamba":
        p["mixer"] = mb.mamba_spec(cfg, dtype)
        # mamba1 blocks subsume the FFN: no second sublayer when d_ff == 0
        if cfg.d_ff > 0:
            p["ln2"] = rmsnorm_spec(cfg.d_model)
            p["ffn"] = (moe_mod.moe_spec(cfg, dtype) if use_moe
                        else mlp_spec(cfg.d_model, cfg.d_ff, cfg.activation, dtype))
    else:
        p["mixer"] = attn.attention_spec(cfg, dtype)
        p["ln2"] = rmsnorm_spec(cfg.d_model)
        p["ffn"] = (moe_mod.moe_spec(cfg, dtype) if use_moe
                    else mlp_spec(cfg.d_model, cfg.d_ff, cfg.activation, dtype))
    return p


def _window_for(cfg, kind: str) -> Optional[int]:
    return cfg.sliding_window if kind == "local" else None


def block_forward(p, x, cfg, kind: str, use_moe: bool, positions,
                  ) -> Tuple[jax.Array, Dict, Dict]:
    """Full-sequence pass. Returns (x, cache_entry, aux)."""
    aux = {}
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if kind == "mamba":
        y, state = mb.mamba_forward(p["mixer"], h, cfg)
        cache = {"conv": state[0], "h": state[1]}
    else:
        q, k, v = attn.qkv_project(p["mixer"], cfg, h, positions)
        y = attn.full_attention(p["mixer"], cfg, q, k, v, causal=True,
                                window=_window_for(cfg, kind))
        y = attn.attention_out(p["mixer"], y, cfg.num_heads)
        cache = {"k": k, "v": v}
    x = x + y

    if "ffn" in p:
        h = rmsnorm(p["ln2"], x, cfg.norm_eps)
        if use_moe:
            y, aux = moe_mod.moe_ffn(p["ffn"], h, cfg)
        else:
            y = mlp(p["ffn"], h, cfg.activation)
        x = x + y
    return x, cache, aux


def block_decode(p, x, cache, cache_len, cfg, kind: str, use_moe: bool,
                 pages=None, attn_impl: str = "gather", layer=None
                 ) -> Tuple[jax.Array, Dict]:
    """One-token pass. x [B,1,D]; cache entry as built by block_forward
    (k/v padded to max length for attention layers).

    ``cache_len`` is either a scalar (whole-batch decode, the legacy
    engine) or an ``[B]`` vector of per-row lengths (slot-pool serving:
    every row is an independent request at its own depth). Vector rows
    whose length is out of range (retired slots) drop their cache write.

    ``pages`` ([B, P] int32 block table) switches attention layers to the
    paged layout: the cache entry's k/v are [num_pages, ps, KV, hd]
    arenas shared by all rows, the new token scatters into row b's page
    at flat position ``cache_len[b]``, and attention gathers the row's
    pages back into position order (kv_pages.PagedSlotPool). Mamba state
    has no time axis and stays slot-dense either way.

    ``attn_impl`` selects the paged read path: ``"gather"`` (the
    executable reference) or ``"fused"`` (one-pass Pallas block-table
    walk, kernels/paged_attention, DESIGN.md §16). Contiguous-layout
    decode ignores it.

    ``layer`` (a traced int32 scalar) says the cache leaves are the
    layer-stacked ones the LM's layer scan carries (``[n_periods, ...]``
    ahead of every shape above): the block writes its new K/V or state
    into that layer's cells of the stacked leaves and reads the layer
    back from them, so the stacked cache is updated in place and no
    whole-arena slice or write-back exists. ``None``: the leaves hold
    this layer alone (the unrolled leftover layers).
    """
    cl = jnp.asarray(cache_len)
    lead = attn.layer_prefix(layer)
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if kind == "mamba":
        y, state = mb.mamba_decode_step(
            p["mixer"], h, (cache["conv"][lead], cache["h"][lead]), cfg)
        new_cache = {"conv": state[0], "h": state[1]}
        if layer is not None:
            new_cache = {n: cache[n].at[layer].set(new_cache[n])
                         for n in new_cache}
    else:
        if cl.ndim == 1:
            positions = cl[:, None].astype(jnp.int32)
        else:
            positions = jnp.full((x.shape[0], 1), cl, jnp.int32)
        q, k, v = attn.qkv_project(p["mixer"], cfg, h, positions)
        if pages is not None:
            if cl.ndim != 1:
                raise ValueError("paged decode requires per-row [B] lens")
            k_cache = attn.scatter_page_token(cache["k"], pages, cl,
                                              k[:, 0], layer)
            v_cache = attn.scatter_page_token(cache["v"], pages, cl,
                                              v[:, 0], layer)
            y = attn.paged_decode_attention(
                p["mixer"], cfg, q, k_cache, v_cache, pages, cl + 1,
                window=_window_for(cfg, kind), impl=attn_impl, layer=layer)
        else:
            if cl.ndim == 1:
                rows = jnp.arange(x.shape[0])
                k_cache = cache["k"].at[lead + (rows, cl)].set(
                    k[:, 0].astype(cache["k"].dtype), mode="drop")
                v_cache = cache["v"].at[lead + (rows, cl)].set(
                    v[:, 0].astype(cache["v"].dtype), mode="drop")
            else:
                start = lead + (0, cl, 0, 0)
                unit = (1,) * len(lead)
                k_cache = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype).reshape(
                        unit + k.shape), start)
                v_cache = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype).reshape(
                        unit + v.shape), start)
            y = attn.cached_decode_attention(
                p["mixer"], cfg, q, k_cache[lead], v_cache[lead], cl + 1,
                window=_window_for(cfg, kind))
        y = attn.attention_out(p["mixer"], y, cfg.num_heads)
        new_cache = {"k": k_cache, "v": v_cache}
    x = x + y

    if "ffn" in p:
        h = rmsnorm(p["ln2"], x, cfg.norm_eps)
        if use_moe:
            y, _ = moe_mod.moe_ffn(p["ffn"], h, cfg)
        else:
            y = mlp(p["ffn"], h, cfg.activation)
        x = x + y
    return x, new_cache


def block_prefill_chunk(p, x, cache, cfg, kind: str, use_moe: bool,
                        positions, write_pos, pages=None, layer=None
                        ) -> Tuple[jax.Array, Dict]:
    """Chunked-prefill pass: C prompt tokens per row against the cache.

    x [B,C,D]; ``positions`` [B,C] are the tokens' absolute positions
    (rope + causal masking); ``write_pos`` [B,C] are the cache positions
    their K/V scatter to — normally equal to ``positions``, but pad
    lanes (a partial last chunk) and rows not advancing this round carry
    the engine's drop sentinel (a huge positive index: out-of-range
    writes drop in both layouts, and positive because JAX wraps negative
    indices into valid cells).

    K/V are scattered *before* attention reads the cache
    (scatter-then-attend), so a query at position i always sees
    positions <= i regardless of chunk partitioning — chunk-size
    invariance is structural, not numeric luck. The cache's ``len``
    vector is untouched: the prefill cursor is engine state.

    Attention layers only: Mamba prefill is recurrent (state at i needs
    the state at i-1, not the cache), so chunking it is a different
    algorithm — the engine gates chunked mode to attention-pure archs.
    ``layer`` indexes layer-stacked cache leaves as in ``block_decode``.
    """
    if kind == "mamba":
        raise ValueError("chunked prefill requires attention layers — "
                         "mamba prefill is recurrent and cannot resume "
                         "from a KV cache")
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, k, v = attn.qkv_project(p["mixer"], cfg, h, positions)
    if pages is not None:
        k_cache = attn.scatter_page_tokens(cache["k"], pages, write_pos, k,
                                           layer)
        v_cache = attn.scatter_page_tokens(cache["v"], pages, write_pos, v,
                                           layer)
        y = attn.paged_chunk_attention(
            p["mixer"], cfg, q, k_cache, v_cache, pages, positions,
            window=_window_for(cfg, kind), layer=layer)
    else:
        lead = attn.layer_prefix(layer)
        rows = jnp.arange(x.shape[0])[:, None]
        k_cache = cache["k"].at[lead + (rows, write_pos)].set(
            k.astype(cache["k"].dtype), mode="drop")
        v_cache = cache["v"].at[lead + (rows, write_pos)].set(
            v.astype(cache["v"].dtype), mode="drop")
        y = attn.cached_chunk_attention(
            p["mixer"], cfg, q, k_cache[lead], v_cache[lead], positions,
            window=_window_for(cfg, kind))
    y = attn.attention_out(p["mixer"], y, cfg.num_heads)
    x = x + y

    if "ffn" in p:
        h = rmsnorm(p["ln2"], x, cfg.norm_eps)
        if use_moe:
            y, _ = moe_mod.moe_ffn(p["ffn"], h, cfg)
        else:
            y = mlp(p["ffn"], h, cfg.activation)
        x = x + y
    return x, {"k": k_cache, "v": v_cache}


def period_layout(cfg):
    """[(kind, use_moe)] for one period, honoring moe.every_n_layers."""
    out = []
    for j, kind in enumerate(cfg.layer_pattern):
        use_moe = False
        if cfg.moe is not None:
            n = cfg.moe.every_n_layers
            use_moe = j % n == n - 1
        out.append((kind, use_moe))
    return out
