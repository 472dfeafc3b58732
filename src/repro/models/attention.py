"""GQA attention: TP-aware head planning + chunked prefill + cached decode.

Tensor-parallel head planning (the part that makes the roofline honest —
see DESIGN.md §6): with TP = |model| = 16, several assigned archs have
head counts that don't divide it (qwen3 40H, minitron 24H, whisper 12H,
gemma3 4H). Plans, chosen per (arch, mesh) via ``head_plan``:

  'shard'  heads % tp == 0 — shard heads. The full-sequence paths expand
           K/V to one head per query head (``expand_kv`` — the repeat_kv
           trick: keeps every attention tensor rank-4 and head-sharded
           even when kv_heads < tp). The cached reads expand only where
           that sharding needs it (kv_heads % tp != 0); otherwise they
           contract the queries against the KV heads directly
           (``kv_grouped``), so no [B, T, H, hd] K/V is ever built.
  'pad'    pad query heads with zeros to the next tp multiple when the
           waste is <= 1.5x (qwen3 40->48: 1.2x; minitron 24->32, whisper
           12->16: 1.33x). Correctness: padded heads produce garbage
           attention outputs, but the output projection contracts with a
           zero-padded wo, so their contribution is exactly zero.
  'seq'    too few heads to pad (gemma3 4H): replicate attention weights
           and shard the *sequence* dimension of the scores instead
           (activation constraint), computing masked rectangle chunks
           (2x triangle FLOPs); local sliding-window layers instead use
           the banded gather path with exact O(S*W) FLOPs.

Train/prefill paths are differentiable by construction (static scans, no
dynamic-bound loops):

  * ``blocked_attention``  — static (q-block, kv-block) schedule covering
    only the causal lower triangle / window band: exact-triangle FLOPs.
  * ``kv_chunked_attention`` — online softmax over kv chunks with the full
    query resident (seq-shardable; rectangle FLOPs).
  * ``banded_attention``   — gather a [S, W] band of K/V; exact window.

Decode and chunked prefill attend the whole cache in one grouped einsum
(linear in cache length): query head h reads kv head h // G, and each
K/V position is read once per kv head whatever G is. On one device —
every serving path — that is always the form taken.
"""

from __future__ import annotations

import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.ops import paged_decode_fused
from repro.sharding.act import constrain_seq_model, current_tp

from .common import spec
from .layers import head_rmsnorm, head_rmsnorm_spec, rope

NEG_INF = -2.0e38


def attention_spec(cfg, dtype):
    """Physical parameter spec. Under a 'pad' head plan the q/o projections
    are stored with `hp` (tp-aligned) heads; the extra rows are masked to
    zero at apply time (``attention_out``), so they are mathematically
    inert — pure sharding padding. The plan is read from the active
    activation-sharding policy, so specs built while lowering for a mesh
    and specs built for single-device tests are each self-consistent.
    """
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    plan, hp = head_plan(h)
    hq = hp if plan == "pad" else h
    p = {
        "wq": spec((d, hq, hd), ("embed", "heads", "head_dim"), dtype=dtype),
        "wk": spec((d, kv, hd), ("embed", "kv_heads", "head_dim"), dtype=dtype),
        "wv": spec((d, kv, hd), ("embed", "kv_heads", "head_dim"), dtype=dtype),
        "wo": spec((hq, hd, d), ("heads", "head_dim", "embed"), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = spec((hq, hd), ("heads", "head_dim"), dtype=dtype, init="zeros")
        p["bk"] = spec((kv, hd), ("kv_heads", "head_dim"), dtype=dtype, init="zeros")
        p["bv"] = spec((kv, hd), ("kv_heads", "head_dim"), dtype=dtype, init="zeros")
    if cfg.qk_norm:
        p["q_norm"] = head_rmsnorm_spec(hd)
        p["k_norm"] = head_rmsnorm_spec(hd)
    return p


# ---------------------------------------------------------------- planning
def head_plan(n_heads: int, tp: Optional[int] = None) -> Tuple[str, int]:
    """(plan, padded_heads) for this head count under tp-way sharding."""
    tp = tp if tp is not None else current_tp()
    if tp <= 1 or n_heads % tp == 0:
        return "shard", n_heads
    padded = -(-n_heads // tp) * tp
    if padded <= 1.5 * n_heads:
        return "pad", padded
    return "seq", n_heads


def kv_grouped(n_heads: int, n_kv_heads: int, q_heads: int) -> bool:
    """Whether the cached reads contract ``q_heads`` query heads against
    the KV heads directly rather than expanding K/V to ``q_heads``.

    True under a 'shard' plan with unpadded queries whose KV heads split
    evenly over the tp degree of the active activation policy — always
    on one device. Otherwise (a 'pad' or 'seq' plan, or fewer KV heads
    than the tp degree, as in mesh lowerings) the expansion is what
    keeps the attention tensors head-sharded, and it stays."""
    plan, _ = head_plan(n_heads)
    return (plan == "shard" and q_heads == n_heads
            and n_kv_heads % current_tp() == 0)


def expand_kv(kv: jax.Array, n_heads: int, pad_to: int = 0) -> jax.Array:
    """[B,T,KV,hd] -> [B,T,H(p),hd]: one K/V head per query head (+ zero
    heads for padding).

    Kept for head-sharded lowerings only: the full-sequence paths under
    a mesh, and the cached reads where ``kv_grouped`` says no. It
    multiplies the K/V bytes by G, so a single-device cached read never
    takes it.

    Implemented as broadcast+reshape (kv-major head layout, h -> kv = h//g)
    rather than ``jnp.take``: a gather over a sharded kv-head axis makes
    XLA all-gather the whole cache (a 2 GB/layer collective on the decode
    cells — §Perf hillclimb 2), while broadcast/reshape keep the sharding.
    """
    b, t, kvh, hd = kv.shape
    g = n_heads // kvh
    if os.environ.get("REPRO_EXPAND_KV_GATHER"):  # §Perf baseline variant
        out = jnp.take(kv, jnp.arange(n_heads) // g, axis=2)
    elif g == 1:
        out = kv
    else:
        out = jnp.broadcast_to(
            kv[:, :, :, None, :], (b, t, kvh, g, hd)
        ).reshape(b, t, kvh * g, hd)
    if pad_to > n_heads:
        out = jnp.pad(out, ((0, 0), (0, 0), (0, pad_to - n_heads), (0, 0)))
    return out


def pad_heads(q: jax.Array, pad_to: int) -> jax.Array:
    h = q.shape[2]
    if pad_to <= h:
        return q
    return jnp.pad(q, ((0, 0), (0, 0), (0, pad_to - h), (0, 0)))


def _pick_chunk(s: int, target: int = 1024) -> int:
    c = min(s, target)
    while s % c:
        c -= 1
    return c


# -------------------------------------------------------------- projection
def qkv_project(p, cfg, x, positions):
    """x [B,S,D] -> q [B,S,H,hd], k/v [B,S,KV,hd] (rope + qk-norm applied)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = head_rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = head_rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_out(p, y, n_heads: int):
    """y [B,S,Hq,hd] -> [B,S,D]. When the projection is head-padded, rows
    >= n_heads of wo are masked to zero so the padded heads contribute
    exactly nothing (and receive no functional gradient coupling)."""
    wo = p["wo"]
    hq = wo.shape[0]
    if hq > n_heads:
        mask = (jnp.arange(hq) < n_heads).astype(wo.dtype)
        wo = wo * mask[:, None, None]
    return jnp.einsum("bshk,hkd->bsd", y, wo)


# ----------------------------------------------------- full-sequence paths
def _pair_list(nq: int, nk: int, cq: int, ck: int, causal: bool,
               window: Optional[int]):
    """Static (q_block, kv_block) schedule: only blocks that can attend."""
    pairs = []
    for qi in range(nq):
        if causal:
            hi = qi + 1
            lo = 0
            if window is not None:
                lo = max(0, (qi * cq - window + 1) // ck)
            lo = min(lo, hi)
        else:
            lo, hi = 0, nk
        for j in range(lo, hi):
            pairs.append((qi, j))
    return pairs


def blocked_attention(
    q: jax.Array,            # [B, S, H, hd]   (H already tp-aligned)
    k: jax.Array,            # [B, T, H, hd]   (pre-expanded)
    v: jax.Array,            # [B, T, H, hd]
    *,
    causal: bool,
    window: Optional[int] = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Static-schedule online-softmax attention; exact triangle/window
    FLOPs; differentiable. Returns [B, S, H, hd]."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    cq = _pick_chunk(s, q_chunk)
    ck = _pick_chunk(t, kv_chunk)
    nq, nk = s // cq, t // ck
    scale = 1.0 / math.sqrt(hd)

    qg = jnp.moveaxis((q * scale).reshape(b, nq, cq, h, hd), 1, 0)
    iota_q = jnp.arange(cq)
    iota_k = jnp.arange(ck)
    pairs = jnp.asarray(_pair_list(nq, nk, cq, ck, causal, window), jnp.int32)

    m0 = jnp.full((nq, b, cq, h), NEG_INF, jnp.float32)
    l0 = jnp.zeros((nq, b, cq, h), jnp.float32)
    acc0 = jnp.zeros((nq, b, cq, h, hd), jnp.float32)

    def body(state, pair):
        m_all, l_all, acc_all = state
        qi, j = pair[0], pair[1]
        qb = jax.lax.dynamic_index_in_dim(qg, qi, 0, keepdims=False)
        m = jax.lax.dynamic_index_in_dim(m_all, qi, 0, keepdims=False)
        l = jax.lax.dynamic_index_in_dim(l_all, qi, 0, keepdims=False)
        acc = jax.lax.dynamic_index_in_dim(acc_all, qi, 0, keepdims=False)
        kc = jax.lax.dynamic_slice(k, (0, j * ck, 0, 0), (b, ck, h, hd))
        vc = jax.lax.dynamic_slice(v, (0, j * ck, 0, 0), (b, ck, h, hd))

        sc = jnp.einsum("bqhd,bchd->bqhc", qb, kc).astype(jnp.float32)
        if causal:
            qpos = qi * cq + iota_q
            kpos = j * ck + iota_k
            ok = kpos[None, :] <= qpos[:, None]
            if window is not None:
                ok &= kpos[None, :] > qpos[:, None] - window
            sc = jnp.where(ok[None, :, None, :], sc, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqhc,bchd->bqhd", p.astype(v.dtype), vc)
        acc_new = acc * alpha[..., None] + pv.astype(jnp.float32)

        upd = lambda a, x: jax.lax.dynamic_update_index_in_dim(a, x, qi, 0)
        return (upd(m_all, m_new), upd(l_all, l_new), upd(acc_all, acc_new)), 0

    (m_all, l_all, acc_all), _ = jax.lax.scan(body, (m0, l0, acc0), pairs)
    out = acc_all / jnp.maximum(l_all[..., None], 1e-37)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)
    return out.astype(q.dtype)


def kv_chunked_attention(
    q: jax.Array,            # [B, S, H, hd]
    k: jax.Array,            # [B, T, H, hd]
    v: jax.Array,            # [B, T, H, hd]
    *,
    causal: bool,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Online softmax over kv chunks with the full query resident — the
    sequence dim stays intact, so an activation constraint can shard it
    over the model axis ('seq' head plan). Rectangle FLOPs when causal."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    ck = _pick_chunk(t, kv_chunk)
    nk = t // ck
    scale = 1.0 / math.sqrt(hd)
    qs = constrain_seq_model(q * scale)
    qpos = jnp.arange(s)
    iota_k = jnp.arange(ck)

    m0 = constrain_seq_model(jnp.full((b, s, h), NEG_INF, jnp.float32))
    l0 = constrain_seq_model(jnp.zeros((b, s, h), jnp.float32))
    acc0 = constrain_seq_model(jnp.zeros((b, s, h, hd), jnp.float32))

    def body(state, j):
        m, l, acc = state
        kc = jax.lax.dynamic_slice(k, (0, j * ck, 0, 0), (b, ck, h, hd))
        vc = jax.lax.dynamic_slice(v, (0, j * ck, 0, 0), (b, ck, h, hd))
        sc = jnp.einsum("bqhd,bchd->bqhc", qs, kc).astype(jnp.float32)
        if causal:
            kpos = j * ck + iota_k
            ok = kpos[None, :] <= qpos[:, None]
            sc = jnp.where(ok[None, :, None, :], sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqhc,bchd->bqhd", p.astype(v.dtype), vc)
        acc_new = acc * alpha[..., None] + pv.astype(jnp.float32)
        return (constrain_seq_model(m_new), constrain_seq_model(l_new),
                constrain_seq_model(acc_new)), 0

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), jnp.arange(nk))
    out = acc / jnp.maximum(l[..., None], 1e-37)
    return out.astype(q.dtype)


def banded_attention(
    q: jax.Array,            # [B, S, H, hd]
    k: jax.Array,            # [B, S, H, hd]
    v: jax.Array,            # [B, S, H, hd]
    *,
    window: int,
) -> jax.Array:
    """Exact sliding-window attention via a gathered [S, W] K/V band —
    O(S*W) FLOPs and memory, seq-shardable (local layers, 'seq' plan)."""
    b, s, h, hd = q.shape
    w = min(window, s)
    scale = 1.0 / math.sqrt(hd)
    pos = jnp.arange(s)
    band = pos[:, None] - (w - 1) + jnp.arange(w)[None, :]   # [S, W]
    valid = band >= 0
    band_c = jnp.clip(band, 0, s - 1)

    kb = jnp.take(k, band_c, axis=1)   # [B, S, W, H, hd]
    vb = jnp.take(v, band_c, axis=1)
    qs = constrain_seq_model(q * scale)
    sc = jnp.einsum("bqhd,bqwhd->bqhw", qs, kb).astype(jnp.float32)
    sc = jnp.where(valid[None, :, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bqhw,bqwhd->bqhd", p.astype(v.dtype), vb)
    return out.astype(q.dtype)


# ------------------------------------------------------------------ decode
#
# Two cache layouts reach the decode path:
#
#   * contiguous — one [B, T, KV, hd] row per slot (kv_slots.SlotPool);
#   * paged      — one [num_pages, page_size, KV, hd] arena shared by all
#     slots plus a per-row block table [B, P] of page ids
#     (kv_pages.PagedSlotPool). ``PAGE_SENTINEL`` rows of the table are
#     unallocated: reads clip (the garbage is masked by the length
#     check), writes drop.
#
# The paged helpers keep flat position order — page j of a row covers
# positions [j*ps, (j+1)*ps) — so the gathered view feeds the same
# ``decode_attention`` masking as the contiguous layout.

#: Name scope around the paged read path (gather or fused, decode or
#: chunk): op metadata only, so a profiler trace can attribute device
#: time to the attention read whatever its ops are called.
PAGED_ATTENTION_SCOPE = "paged_attention"


def layer_prefix(layer) -> tuple:
    """Index prefix selecting one layer of a layer-stacked cache leaf
    (``[n_periods, ...]``, as the LM's layer scan carries it), or the
    empty prefix for a leaf that holds one layer alone."""
    return () if layer is None else (layer,)


def gather_pages(arena: jax.Array, pages: jax.Array,
                 layer: Optional[jax.Array] = None) -> jax.Array:
    """[num_pages, ps, ...] arena + [B, P] block table -> [B, P*ps, ...]
    per-row contiguous view. Sentinel/unallocated entries clip to the
    last page; its contents are garbage for this row but lie beyond the
    row's true length, so the decode mask hides them. With ``layer``
    the arena is layer-stacked ([L, num_pages, ps, ...]) and the view
    is read as ``arena[layer, table]`` in one gather: the layer's arena
    is never sliced out on its own."""
    lead = layer_prefix(layer)
    num_pages = arena.shape[len(lead)]
    g = arena[lead + (jnp.clip(pages, 0, num_pages - 1),)]
    b, np_, ps = g.shape[:3]
    return g.reshape((b, np_ * ps) + g.shape[3:])


def copy_pages(arena: jax.Array, src: jax.Array, dst: jax.Array,
               axis: int = 0) -> jax.Array:
    """Copy whole pages ``src[i] -> dst[i]`` within one arena — the
    device half of a copy-on-write split (kv_pages.PagedSlotPool's
    split pass). arena [..., num_pages, ps, ...] with the page axis at
    ``axis`` (periods-stacked families carry leading layer axes);
    src/dst [n] int32.

    The copy is page-granular and runs to completion before the next
    decode dispatch reads the arena, so — together with the split
    invariant ("a shared page is never written; a written page has
    refcount 1", DESIGN.md §11) — readers of the *original* page never
    observe a partially-split page: the writer's block table is simply
    repointed at the finished copy."""
    idx = (slice(None),) * axis + (dst,)
    return arena.at[idx].set(jnp.take(arena, src, axis=axis))


def scatter_page_token(arena: jax.Array, pages: jax.Array, pos: jax.Array,
                       val: jax.Array,
                       layer: Optional[jax.Array] = None) -> jax.Array:
    """Write ``val[b]`` at flat position ``pos[b]`` of row b's paged
    cache. arena [num_pages, ps, ...]; pages [B, P]; pos [B]; val [B, ...].
    Writes addressed past the block table or into sentinel (unallocated)
    entries drop — the paged analogue of the contiguous layout's
    out-of-range ``mode="drop"`` update. Under copy-on-write prefix
    sharing the engine guarantees the table this scatter reads is the
    *post-split* one: a row whose write would land in a shared
    (refcount > 1) page is either split before the dispatch or has its
    table row sentinel-masked for the round, so a scatter can never
    write a page another slot still reads. ``layer`` as in
    :func:`scatter_page_tokens`."""
    return scatter_page_tokens(arena, pages, pos[:, None], val[:, None],
                               layer)


def scatter_page_tokens(arena: jax.Array, pages: jax.Array, pos: jax.Array,
                        val: jax.Array,
                        layer: Optional[jax.Array] = None) -> jax.Array:
    """Chunk form of :func:`scatter_page_token`: write ``val[b, c]`` at
    flat position ``pos[b, c]`` of row b's paged cache. arena
    [num_pages, ps, ...]; pages [B, P]; pos [B, C]; val [B, C, ...].
    Lanes whose position lies past the block table (in particular the
    engine's drop sentinel — a huge *positive* position, never negative,
    because JAX wraps negative indices) or in a sentinel table entry
    drop, exactly as the single-token scatter. Within one chunk the
    engine feeds strictly increasing positions per row, so no two lanes
    alias one (page, offset) cell. With ``layer`` the arena is
    layer-stacked ([L, num_pages, ps, ...]) and the values land in
    ``arena[layer, page, offset]``: one scatter into the stacked arena,
    which XLA updates in place."""
    lead = layer_prefix(layer)
    num_pages, ps = arena.shape[len(lead)], arena.shape[len(lead) + 1]
    p_cap = pages.shape[1]
    page_idx = pos // ps                                         # [B, C]
    page = jnp.take_along_axis(
        pages, jnp.clip(page_idx, 0, p_cap - 1), axis=1)         # [B, C]
    page = jnp.where((page_idx >= 0) & (page_idx < p_cap), page, num_pages)
    return arena.at[lead + (page, pos % ps)].set(val.astype(arena.dtype),
                                                 mode="drop")


def _grouped_attend(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                    valid: jax.Array) -> jax.Array:
    """q [B,C,H,hd] against k/v [B,T,KV,hd] (H a multiple of KV; KV == H
    for an expanded cache) under ``valid`` [B or 1, C, T]. Query head h
    reads kv head h // G (kv-major, as ``expand_kv`` and the fused
    kernel): q is viewed as [B,C,KV,G,hd] and contracted against the
    KV heads, so each cached K/V position is read once per kv head.
    Rounding is the expanded form's: scores and output in the cache's
    dtype, the softmax in float32."""
    b, c, h, hd = q.shape
    kvh = k_cache.shape[2]
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)

    qg = (q * scale).reshape(b, c, kvh, g, hd)
    sc = jnp.einsum("bckgd,btkd->bckgt", qg, k_cache).astype(jnp.float32)
    sc = jnp.where(valid[:, :, None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bckgt,btkd->bckgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(b, c, h, hd).astype(q.dtype)


def decode_attention(
    q: jax.Array,            # [B, 1, H(p), hd]
    k_cache: jax.Array,      # [B, T, KV, hd], or [B, T, Hp, hd] expanded
    v_cache: jax.Array,
    cache_len: jax.Array,
    *,
    window: Optional[int] = None,
) -> jax.Array:
    """Single-token attention over the cache (linear in T)."""
    t = k_cache.shape[1]
    pos = jnp.arange(t)
    cl = jnp.asarray(cache_len)
    cl = cl[:, None] if cl.ndim == 1 else cl[None, None]
    valid = pos[None, :] < cl
    if window is not None:
        valid &= pos[None, :] >= cl - window
    return _grouped_attend(q, k_cache, v_cache, valid[:, None, :])


def chunk_decode_attention(
    q: jax.Array,            # [B, C, H(p), hd]
    k_cache: jax.Array,      # [B, T, KV, hd], or [B, T, Hp, hd] expanded
    v_cache: jax.Array,
    q_positions: jax.Array,  # [B, C] absolute position of each query
    *,
    window: Optional[int] = None,
) -> jax.Array:
    """Chunked-prefill attention over the cache: each query at absolute
    position p attends cache positions <= p (its own K/V was scattered
    into the cache *before* this read — scatter-then-attend), so the
    result at a position is independent of how the prompt was chunked.
    ``decode_attention`` is the C == 1 case with q_positions == cache_len
    - 1; there is no separate length mask because positions > p are
    either unwritten (masked here) or another row's concern (gathered
    views are per-row). Pad lanes of a partial last chunk carry garbage
    positions; their outputs are discarded and their writes dropped by
    the engine, so they never influence a real lane."""
    t = k_cache.shape[1]
    pos = jnp.arange(t)
    qp = jnp.asarray(q_positions)
    valid = pos[None, None, :] <= qp[:, :, None]                 # [B, C, T]
    if window is not None:
        valid &= pos[None, None, :] > qp[:, :, None] - window
    return _grouped_attend(q, k_cache, v_cache, valid)


# --------------------------------------------------------------- dispatch
def full_attention(p, cfg, q, k, v, *, causal: bool,
                   window: Optional[int]) -> jax.Array:
    """Pick the path from the head plan; q comes from the (possibly
    head-padded) projection, so q.shape[2] is already tp-aligned under a
    'pad' plan. Returns the pre-wo tensor [B,S,Hq,hd]."""
    h = cfg.num_heads
    plan, _ = head_plan(h)
    hq = q.shape[2]
    if plan in ("shard", "pad"):
        ke = expand_kv(k, h, pad_to=hq)
        ve = expand_kv(v, h, pad_to=hq)
        return blocked_attention(q, ke, ve, causal=causal, window=window)
    # 'seq' plan: replicated heads, sequence-sharded scores
    ke = expand_kv(k, h)
    ve = expand_kv(v, h)
    if window is not None and causal:
        return banded_attention(q, ke, ve, window=window)
    return kv_chunked_attention(q, ke, ve, causal=causal)


def _cache_heads(cfg, q, k_cache, v_cache):
    """The cache as the read contracts it: as stored ([.., KV, hd]) where
    ``kv_grouped`` holds, else expanded to q's (tp-aligned) heads."""
    h, hq = cfg.num_heads, q.shape[2]
    if kv_grouped(h, k_cache.shape[2], hq):
        return k_cache, v_cache
    return expand_kv(k_cache, h, pad_to=hq), expand_kv(v_cache, h, pad_to=hq)


def cached_decode_attention(p, cfg, q, k_cache, v_cache, cache_len, *,
                            window: Optional[int]) -> jax.Array:
    k, v = _cache_heads(cfg, q, k_cache, v_cache)
    return decode_attention(q, k, v, cache_len, window=window)


def paged_decode_attention(p, cfg, q, k_arena, v_arena, pages, cache_len, *,
                           window: Optional[int],
                           impl: str = "gather",
                           layer: Optional[jax.Array] = None) -> jax.Array:
    """Block-table decode, two executable implementations:

    ``impl="gather"`` (the reference): gather each row's pages into a
    contiguous [B, P*ps, KV, hd] view, then attend exactly as the
    contiguous layout (same masking, same per-row length semantics).
    Every gathered page round-trips HBM twice — once for the gather's
    materialized view, once for attention to read it back (once per kv
    head: on one device the G query heads of a group are contracted
    against it together, ``kv_grouped``).

    ``impl="fused"``: one Pallas kernel walks the block table per
    (row, kv-head) and computes online-softmax attention in a single
    pass (kernels/paged_attention, DESIGN.md §16) — each page crosses
    HBM once, GQA groups share the page load, and sentinel-masked
    table rows contribute exactly nothing (the engine's paused/frozen
    slots). The interpret-tier differential suite pins it to the
    gather path; serving selects it via
    ``SlotServeEngine(attention_impl="fused")``.

    Both run under the ``PAGED_ATTENTION_SCOPE`` name scope. ``layer``
    indexes layer-stacked arenas ([L, num_pages, ps, KV, hd]): both
    paths read the layer's pages straight from the stacked arena.
    """
    if impl not in ("gather", "fused"):
        raise ValueError(f"unknown paged decode impl {impl!r}; "
                         f"expected 'gather' or 'fused'")
    with jax.named_scope(PAGED_ATTENTION_SCOPE):
        if impl == "fused":
            return paged_decode_fused(q, k_arena, v_arena, pages, cache_len,
                                      cfg.num_heads, window=window,
                                      layer=layer)
        kb = gather_pages(k_arena, pages, layer)
        vb = gather_pages(v_arena, pages, layer)
        return cached_decode_attention(p, cfg, q, kb, vb, cache_len,
                                       window=window)


def cached_chunk_attention(p, cfg, q, k_cache, v_cache, q_positions, *,
                           window: Optional[int]) -> jax.Array:
    k, v = _cache_heads(cfg, q, k_cache, v_cache)
    return chunk_decode_attention(q, k, v, q_positions, window=window)


def paged_chunk_attention(p, cfg, q, k_arena, v_arena, pages, q_positions, *,
                          window: Optional[int],
                          layer: Optional[jax.Array] = None) -> jax.Array:
    """Block-table chunked prefill: gather the row's pages into position
    order, then attend at each query's absolute position (same gathered
    view and masking family as ``paged_decode_attention``, and the same
    name scope, and the same ``layer``)."""
    with jax.named_scope(PAGED_ATTENTION_SCOPE):
        kb = gather_pages(k_arena, pages, layer)
        vb = gather_pages(v_arena, pages, layer)
        return cached_chunk_attention(p, cfg, q, kb, vb, q_positions,
                                      window=window)


def naive_reference_attention(q, k, v, *, causal, window=None):
    """O(S^2)-memory GQA oracle used only by tests. q [B,S,H,hd];
    k/v [B,T,KV,hd]."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    ke = expand_kv(k, h)
    ve = expand_kv(v, h)
    sc = jnp.einsum("bqhd,bthd->bqht", q, ke).astype(jnp.float32)
    sc = sc / math.sqrt(hd)
    if causal:
        qpos = jnp.arange(s)[:, None]
        kpos = jnp.arange(t)[None, :]
        ok = kpos <= qpos
        if window is not None:
            ok &= kpos > qpos - window
        sc = jnp.where(ok[None, :, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bqht,bthd->bqhd", p.astype(v.dtype), ve)
    return out.reshape(b, s, h, hd).astype(q.dtype)
