"""Fused paged-decode attention as a Pallas TPU kernel.

The gather-then-attend path (models/attention.py::paged_decode_attention)
materializes every gathered page in HBM — one full pass to build the
[B, P*ps, KV, hd] contiguous view, a second for attention to read it
back. The paper's principle is to minimize slow memory-system round
trips per operation; flashinfer's ``BatchDecodeWithPagedKVCacheWrapper``
(SNIPPETS.md #1) shows the production shape: one kernel that walks the
block table ``(page_indices, last_page_len)`` per head and computes
attention in a single pass, so each page of K/V crosses HBM exactly
once.

Walk order (DESIGN.md §16): grid ``(B,)`` — one program per row. It
holds the row's ``H = KV * G`` query vectors (kv-major, the grouping
``expand_kv`` defines: query head ``h`` reads kv head ``h // G``) and
walks the row's live block-table entries in flat position order — page
``j`` covers positions ``[j*ps, (j+1)*ps)`` — accumulating online
softmax ``(m, l, acc)`` per query head. GQA is what makes the fusion
pay: all ``G`` queries of a group score against one page load.

Memory layout: the layer index, the block table and the row lengths
are scalar-prefetch operands (SMEM), so page ids are scalars the kernel
indexes HBM with. The K/V arenas stay in HBM (``memory_space=pl.ANY``),
layer-stacked ``[L, num_pages, ps, KV, hd]`` as the model's layer scan
carries them, so no layer's arena is sliced out for the call; each loop
step DMAs one whole page ``[ps, KV, hd]`` of the layer into VMEM
scratch and waits for it. A page is the smallest legal copy: one kv
head's ``[ps, hd]`` slice cuts the second-minor KV axis to 1, which the
TPU tiling refuses,
and viewing the arena as ``[pages, ps, KV * hd]`` makes XLA relayout
the whole arena on every call. In VMEM the page is flattened to
``[ps * KV, hd]`` and every query head scores against all of it, with
the scores of mismatched heads masked; decode is bandwidth-bound, so
the wasted MXU work is free while each page crosses HBM once per row.
Only pages that hold positions the row attends are walked:
``[lo, ceil(len / ps))`` with ``lo`` the first page of the sliding
window. The copy is synchronous — double buffering and a head-major
arena are ROADMAP S3.

Sentinel handling: a table entry ``>= num_pages`` is unallocated (or
masked for the round by the engine — paused rows under a starved CoW
split, kv_pages.masked_table). The gather reference *clips* such
entries to the last page and relies on the ``pos < len`` mask to hide
the garbage; this kernel masks the whole page explicitly, so a
fully-sentinel row (every page masked) accumulates ``l == 0`` and
emits exact zeros — paused/frozen slots contribute nothing, and never
read another row's pages.

Numerical shape: scores and the softmax accumulate in float32
regardless of arena dtype; masked lanes are excluded from ``p`` by a
``where`` (not just a ``NEG_INF`` score: when every lane of a page is
masked the running max stays at the ``NEG_INF`` sentinel and
``exp(NEG_INF - NEG_INF) == 1`` would leak weight). The final
division guards ``l == 0`` so all-masked rows divide safely.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG_INF = -2.0e38  # matches models/attention.py's masking sentinel


def paged_decode_kernel(
    layer_ref,    # SMEM (1,) i32: the layer of the stacked arenas
    pages_ref,    # SMEM (B * P,) i32: flattened block tables
    len_ref,      # SMEM (B,) i32: per-row cache lengths
    q_ref,        # (1, H, hd): the row's queries, kv-major (H = KV * G)
    k_hbm,        # HBM (L, num_pages, ps, KV, hd): K arenas
    v_hbm,        # HBM (L, num_pages, ps, KV, hd): V arenas
    o_ref,        # out (1, H, hd)
    k_buf,        # VMEM scratch (ps, KV, hd): one page, every kv head
    v_buf,        # VMEM scratch (ps, KV, hd)
    sem,          # DMA semaphores (2,)
    *,
    num_pages: int,
    page_size: int,
    table_len: int,
    group: int,
    window: Optional[int],
    scale: float,
):
    row = pl.program_id(0)
    layer = layer_ref[0]
    n_heads, hd = q_ref.shape[1], q_ref.shape[2]
    kv = n_heads // group
    width = page_size * kv                  # flattened page: lane c is
    length = len_ref[row]                   # position c // KV, head c % KV
    q = q_ref[0].astype(jnp.float32) * scale                 # [H, hd]
    # query head r reads kv head r // G; the flattened page's lane c
    # holds kv head c % KV. Scores between mismatched heads are masked.
    r = jax.lax.broadcasted_iota(jnp.int32, (n_heads, width), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n_heads, width), 1)
    q_head = sum((r >= i * group).astype(jnp.int32) for i in range(1, kv))
    head_ok = q_head == c % kv
    offset = c // kv                                         # in-page position

    def body(j, carry):
        m, l, acc = carry
        page = pages_ref[row * table_len + j]
        live = page < num_pages                   # sentinel page -> all masked
        pid = jnp.clip(page, 0, num_pages - 1)
        k_copy = pltpu.make_async_copy(k_hbm.at[layer, pid], k_buf,
                                       sem.at[0])
        v_copy = pltpu.make_async_copy(v_hbm.at[layer, pid], v_buf,
                                       sem.at[1])
        k_copy.start()
        v_copy.start()
        k_copy.wait()
        v_copy.wait()
        k = k_buf[...].astype(jnp.float32).reshape(width, hd)
        v = v_buf[...].astype(jnp.float32).reshape(width, hd)
        pos = j * page_size + offset
        ok = head_ok & live & (pos < length)
        if window is not None:
            ok = ok & (pos >= length - window)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [H, ps * KV]
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # exclude masked lanes explicitly: with m_new still at NEG_INF,
        # exp(NEG_INF - NEG_INF) == 1 would weight a masked lane
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    hi = jnp.minimum((length + page_size - 1) // page_size, table_len)
    lo = (0 if window is None
          else jnp.maximum(length - window, 0) // page_size)
    m0 = jnp.full((n_heads, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n_heads, 1), jnp.float32)
    acc0 = jnp.zeros((n_heads, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    # all-masked rows (fully sentinel table / length 0) have l == 0 and
    # emit exact zeros — they contribute nothing downstream
    out = acc / jnp.maximum(l, 1e-37)
    o_ref[0] = out.astype(o_ref.dtype)


def fused_paged_decode(
    q: jax.Array,          # [B, KV, G, hd] kv-major grouped queries
    k_arena: jax.Array,    # [num_pages, ps, KV, hd], or [L, ...] stacked
    v_arena: jax.Array,    # same shape as k_arena
    pages: jax.Array,      # [B, P] i32 block tables (sentinel = num_pages)
    cache_len: jax.Array,  # [B] i32 per-row lengths
    *,
    layer: Optional[jax.Array] = None,
    window: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One-pass block-table decode attention. Returns [B, KV, G, hd].
    ``layer`` picks the layer of layer-stacked arenas; without it the
    arenas hold one layer."""
    if layer is None:
        k_arena, v_arena, layer = k_arena[None], v_arena[None], 0
    b, kv, g, hd = q.shape
    num_pages, ps = k_arena.shape[1], k_arena.shape[2]
    p_cap = pages.shape[1]
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        paged_decode_kernel, num_pages=num_pages, page_size=ps,
        table_len=p_cap, group=g, window=window, scale=scale)
    arena = pl.BlockSpec(memory_space=pl.ANY)
    rows = pl.BlockSpec((1, kv * g, hd), lambda i, *_: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[rows, arena, arena],
        out_specs=rows,
        scratch_shapes=[
            pltpu.VMEM((ps, kv, hd), k_arena.dtype),
            pltpu.VMEM((ps, kv, hd), v_arena.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv * g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=kernels.resolve_interpret(interpret),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      pages.astype(jnp.int32).reshape(-1), cache_len.astype(jnp.int32),
      q.reshape(b, kv * g, hd), k_arena, v_arena)
    return out.reshape(b, kv, g, hd)
