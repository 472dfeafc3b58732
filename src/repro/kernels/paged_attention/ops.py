"""Model-facing wrapper for the fused paged-decode kernel.

Bridges the decode path's shapes — q ``[B, 1, Hq, hd]`` (possibly
head-padded for tensor parallelism), arena ``[num_pages, ps, KV, hd]``,
block table ``[B, P]``, per-row lengths ``[B]`` — to the kernel's
kv-major ``[B, KV, G, hd]`` grouping and back. A layer-stacked arena
``[L, num_pages, ps, KV, hd]`` comes with its ``layer`` index and is
handed to the kernel whole. Under a 'pad' head plan
the padded query heads are dropped before the kernel and re-padded
with zeros after: the output projection masks their ``wo`` rows to
zero, so zeros are exactly what the gather path computes for them too.

``interpret=None`` leaves the tier to the platform
(``repro.kernels.default_interpret``): the CI/CPU tier runs the kernel
under the Pallas interpreter (the differential suite pins it to the
gather reference there); a TPU backend compiles it.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import fused_paged_decode


def paged_decode_fused(
    q: jax.Array,          # [B, 1, Hq, hd] (Hq >= H when head-padded)
    k_arena: jax.Array,    # [num_pages, ps, KV, hd], or [L, ...] stacked
    v_arena: jax.Array,    # same shape as k_arena
    pages: jax.Array,      # [B, P] i32
    cache_len: jax.Array,  # [B] i32 (or scalar; broadcast per row)
    n_heads: int,
    *,
    window: Optional[int] = None,
    layer: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused one-pass paged decode attention. Returns [B, 1, Hq, hd],
    shape- and dtype-identical to the gather path's output."""
    b, s, hq, hd = q.shape
    if s != 1:
        raise ValueError("fused paged decode is single-token (q [B,1,H,hd])")
    kv = k_arena.shape[-2]
    if n_heads % kv:
        raise ValueError(f"num_heads {n_heads} not divisible by "
                         f"num_kv_heads {kv}")
    g = n_heads // kv
    cl = jnp.asarray(cache_len, jnp.int32)
    if cl.ndim == 0:
        cl = jnp.broadcast_to(cl, (b,))
    # kv-major grouping: expanded head h reads kv head h // g, so the
    # true heads reshape directly to [B, KV, G, hd]
    qg = q[:, 0, :n_heads, :].reshape(b, kv, g, hd)
    out = fused_paged_decode(qg, k_arena, v_arena, pages, cl, layer=layer,
                             window=window, interpret=interpret)
    out = out.reshape(b, 1, n_heads, hd)
    if hq > n_heads:
        out = jnp.pad(out, ((0, 0), (0, 0), (0, hq - n_heads), (0, 0)))
    return out
