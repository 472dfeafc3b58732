"""Decoder-only language model (dense / MoE / SSM / hybrid / VLM backbone).

Parameters are organized as:

  embed/…            token embeddings (skipped for stub-frontend families,
                     which receive precomputed embeddings)
  periods/layer_<j>  per-pattern-position params, stacked over n_periods
                     with a leading 'layer' axis — applied under lax.scan
  leftover/layer_<j> unrolled remainder layers (num_layers % period)
  final_norm, lm_head

The same apply code serves train (full sequence), prefill (full sequence +
cache emission) and decode (single token against the cache).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import blocks
from .common import init_params, shape_params, spec, stack_specs
from .layers import embed, embedding_spec, lm_head_spec, rmsnorm, rmsnorm_spec, unembed
from .mamba import mamba_state_shape
from repro.sharding.act import constrain_batch

PyTree = Any


class LM:
    def __init__(self, cfg):
        self.cfg = cfg
        self.n_periods, self.pattern, self.leftover = cfg.periods()
        self.layout = blocks.period_layout(cfg)
        # Per-period rematerialization: the train-step builder flips this on
        # so the layer-scan body saves only boundary activations (+ the
        # no-batch-dim dots XLA wants for efficient backward).
        self.remat = False

    def _remat_group(self) -> int:
        """sqrt-N group size for two-level remat (1 = flat per-period)."""
        import os
        if os.environ.get("REPRO_FLAT_REMAT"):
            return 1
        np_ = self.n_periods
        if np_ < 16:
            return 1
        g = 1
        for d in range(2, int(np_ ** 0.5) + 1):
            if np_ % d == 0:
                g = d
        return g

    # ----------------------------------------------------------- param spec
    def spec_tree(self) -> PyTree:
        cfg = self.cfg
        dtype = cfg.dtype
        period = {
            f"layer_{j}": blocks.block_spec(cfg, kind, use_moe, dtype)
            for j, (kind, use_moe) in enumerate(self.layout)
        }
        tree: Dict[str, PyTree] = {
            "periods": stack_specs(period, self.n_periods),
            "final_norm": rmsnorm_spec(cfg.d_model),
        }
        if self.leftover:
            tree["leftover"] = {
                f"layer_{j}": blocks.block_spec(cfg, kind, use_moe, dtype)
                for j, (kind, use_moe) in enumerate(self.layout[: len(self.leftover)])
            }
        if cfg.frontend is None:
            tree["embed"] = embedding_spec(cfg.vocab_size, cfg.d_model, dtype)
            if not cfg.tie_embeddings:
                tree["lm_head"] = lm_head_spec(cfg.d_model, cfg.vocab_size, dtype)
        else:
            # Stub frontend: inputs are precomputed embeddings; output head
            # still projects to the vocab.
            tree["lm_head"] = lm_head_spec(cfg.d_model, cfg.vocab_size, dtype)
        return tree

    def init(self, key) -> PyTree:
        return init_params(self.spec_tree(), key)

    def shape_params(self) -> PyTree:
        return shape_params(self.spec_tree())

    # ------------------------------------------------------------- forward
    def _embed_in(self, params, batch):
        cfg = self.cfg
        if cfg.frontend is None:
            x = embed(params["embed"], batch["tokens"]).astype(cfg.dtype)
            if getattr(cfg, "scale_embeddings", False):
                x = x * jnp.sqrt(jnp.asarray(cfg.d_model, x.dtype))
            return constrain_batch(x)
        return constrain_batch(batch["embeds"].astype(cfg.dtype))

    def _unembed(self, params, x):
        cfg = self.cfg
        if cfg.frontend is None and cfg.tie_embeddings:
            return unembed(params["embed"], None, x, tie=True)
        return unembed(None, params["lm_head"], x, tie=False)

    def forward(self, params, batch) -> Tuple[jax.Array, Dict]:
        """Full-sequence forward. Returns (logits [B,S,V], aux)."""
        x, _, aux = self._backbone(params, batch, want_cache=False)
        return self._unembed(params, x), aux

    def _backbone(self, params, batch, *, want_cache: bool):
        cfg = self.cfg
        x = self._embed_in(params, batch)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

        aux_keys = ("moe_aux_loss", "moe_drop_frac") if cfg.moe else ()

        def run_period(x, period_params):
            caches = {}
            aux_sum = {k: jnp.float32(0.0) for k in aux_keys}
            for j, (kind, use_moe) in enumerate(self.layout):
                x, cache, aux = blocks.block_forward(
                    period_params[f"layer_{j}"], x, cfg, kind, use_moe,
                    positions)
                caches[f"layer_{j}"] = cache
                for k in aux_keys:
                    if k in aux:
                        aux_sum[k] = aux_sum[k] + aux[k]
            return x, caches, aux_sum

        def scan_body(carry, period_params):
            x, aux_acc = carry
            x, caches, aux_sum = run_period(x, period_params)
            aux_acc = {k: aux_acc[k] + aux_sum[k] for k in aux_keys}
            return (x, aux_acc), caches if want_cache else 0

        aux0 = {k: jnp.float32(0.0) for k in aux_keys}
        group = self._remat_group() if (self.remat and not want_cache) else 1
        if group > 1:
            # Two-level (sqrt-N) remat for deep stacks: the outer scan saves
            # only one boundary per *group* of `group` periods; the
            # checkpointed group body re-runs its periods in backward (each
            # period itself checkpointed). Activation state drops from
            # n_periods to n_periods/group boundaries at ~+1 extra forward
            # of compute — which lets the big dense models run far fewer
            # microbatches (8x fewer gradient reductions / FSDP gathers;
            # §Perf iteration 4).
            inner = jax.checkpoint(
                scan_body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            grouped = jax.tree_util.tree_map(
                lambda a: a.reshape((self.n_periods // group, group)
                                    + a.shape[1:]),
                params["periods"])

            def group_body(carry, group_params):
                carry, _ = jax.lax.scan(inner, carry, group_params)
                return carry, 0

            (x, aux_acc), period_caches = jax.lax.scan(
                jax.checkpoint(group_body), (x, aux0), grouped)
        else:
            body = scan_body
            if self.remat and not want_cache:
                body = jax.checkpoint(
                    scan_body,
                    policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            (x, aux_acc), period_caches = jax.lax.scan(
                body, (x, aux0), params["periods"])

        leftover_caches = {}
        if self.leftover:
            for j in range(len(self.leftover)):
                kind, use_moe = self.layout[j]
                x, cache, aux = blocks.block_forward(
                    params["leftover"][f"layer_{j}"], x, cfg, kind, use_moe,
                    positions)
                leftover_caches[f"layer_{j}"] = cache
                for k in aux_keys:
                    if k in aux:
                        aux_acc[k] = aux_acc[k] + aux[k]

        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        cache = None
        if want_cache:
            cache = {"periods": period_caches, "leftover": leftover_caches}
        return x, cache, aux_acc

    # ---------------------------------------------------------------- loss
    def loss_fn(self, params, batch) -> Tuple[jax.Array, Dict]:
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        # next-token prediction: logits[t] predicts labels[t]
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones_like(ll)
        loss = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        metrics = {"loss": loss, "tokens": jnp.sum(mask)}
        if "moe_aux_loss" in aux:
            n_moe = sum(1 for _, m in self.layout if m) * self.n_periods
            aux_loss = aux["moe_aux_loss"] / max(n_moe, 1)
            metrics["moe_aux_loss"] = aux_loss
            loss = loss + 0.01 * aux_loss
            metrics["total_loss"] = loss
        return loss, metrics

    # ------------------------------------------------------------- serving
    def prefill(self, params, batch, *, max_len: Optional[int] = None,
                length: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, PyTree]:
        """Run the prompt, build the cache. Returns (last_logits, cache).

        ``length`` ([B] int32) marks per-row true prompt lengths when the
        prompts are right-padded to a fixed shape (slot-pool serving):
        logits come from position ``length - 1`` and ``cache["len"]`` is
        the per-row vector, so decode attends only to real tokens (pad
        K/V beyond ``length`` is masked out by the decode path). Only
        valid for attention layers — a Mamba/hybrid prefill is recurrent
        and must be run at the exact prompt length instead.
        """
        cfg = self.cfg
        x, cache, _ = self._backbone(params, batch, want_cache=True)
        s = (batch["tokens"] if cfg.frontend is None else batch["embeds"]).shape[1]
        if length is None:
            logits = self._unembed(params, x[:, -1:, :])[:, 0]
            ln = jnp.asarray(s, jnp.int32)
        else:
            ln = jnp.asarray(length, jnp.int32)
            rows = jnp.arange(x.shape[0])
            x_last = x[rows, jnp.clip(ln - 1, 0, s - 1)]       # [B, D]
            logits = self._unembed(params, x_last[:, None, :])[:, 0]
        cache = self._pad_cache(cache, s, max_len or s)
        cache["len"] = ln
        return logits, cache

    def _pad_cache(self, cache, s: int, max_len: int):
        def pad_kv(leaf_path_free):  # pad k/v time axis to max_len
            def fn(d):
                if not isinstance(d, dict):
                    return d
                out = {}
                for k, v in d.items():
                    if k in ("k", "v"):
                        # periods-stacked leaves have shape [NP,B,S,KV,hd]
                        t_axis = v.ndim - 3
                        padw = [(0, 0)] * v.ndim
                        padw[t_axis] = (0, max_len - s)
                        out[k] = jnp.pad(v, padw)
                    elif isinstance(v, dict):
                        out[k] = fn(v)
                    else:
                        out[k] = v
                return out
            return fn
        f = pad_kv(None)
        return {"periods": f(cache["periods"]),
                "leftover": f(cache["leftover"])}

    def init_cache(self, batch_size: int, max_len: int,
                   for_shapes: bool = False) -> PyTree:
        """Zero (or ShapeDtypeStruct) decode cache for serve_step lowering."""
        cfg = self.cfg
        kvh, hd = max(cfg.num_kv_heads, 1), max(cfg.resolved_head_dim, 1)

        def entry(kind):
            if kind == "mamba":
                cshape, hshape = mamba_state_shape(cfg, batch_size)
                return {"conv": (cshape, cfg.dtype),
                        "h": (hshape, jnp.float32)}
            return {"k": ((batch_size, max_len, kvh, hd), cfg.dtype),
                    "v": ((batch_size, max_len, kvh, hd), cfg.dtype)}

        def materialize(tree, stack_n=None):
            out = {}
            for name, (shape, dtype) in tree.items():
                full = (stack_n,) + shape if stack_n else shape
                if for_shapes:
                    out[name] = jax.ShapeDtypeStruct(full, dtype)
                else:
                    out[name] = jnp.zeros(full, dtype)
            return out

        periods = {
            f"layer_{j}": materialize(entry(kind), stack_n=self.n_periods)
            for j, (kind, _) in enumerate(self.layout)
        }
        leftover = {
            f"layer_{j}": materialize(entry(self.layout[j][0]))
            for j in range(len(self.leftover))
        }
        ln = (jax.ShapeDtypeStruct((), jnp.int32) if for_shapes
              else jnp.asarray(0, jnp.int32))
        return {"periods": periods, "leftover": leftover, "len": ln}

    def _scan_layers(self, params, cache, x, layer_fn):
        """Run ``layer_fn`` over the stacked periods under one layer scan
        with the ``periods`` cache in the carry: the scan runs over the
        period parameters and the layer index, and each layer updates
        its cells of the stacked cache leaves in place (``layer_fn``
        gets the stacked leaves and the index, ``blocks.block_decode``).
        So no layer's cache is sliced out and written back, and a
        donated cache is updated where it lies. Returns (x, the periods
        cache)."""
        def body(carry, xs):
            x, period_cache = carry
            period_params, layer = xs
            period_cache = dict(period_cache)
            for j, (kind, use_moe) in enumerate(self.layout):
                name = f"layer_{j}"
                x, period_cache[name] = layer_fn(
                    period_params[name], x, period_cache[name], kind,
                    use_moe, layer)
            return (x, period_cache), None

        (x, period_cache), _ = jax.lax.scan(
            body, (x, cache["periods"]),
            (params["periods"], jnp.arange(self.n_periods, dtype=jnp.int32)))
        return x, period_cache

    def _leftover_layers(self, params, cache, x, layer_fn):
        """The unrolled remainder layers, each on its own cache leaves
        (``layer`` None). Returns (x, the leftover cache)."""
        out = {}
        for j in range(len(self.leftover)):
            kind, use_moe = self.layout[j]
            name = f"layer_{j}"
            x, out[name] = layer_fn(params["leftover"][name], x,
                                    cache["leftover"][name], kind, use_moe,
                                    None)
        return x, out

    def prefill_chunk(self, params, cache, tokens, positions, write_pos
                      ) -> Tuple[jax.Array, PyTree]:
        """One chunk of continuous (chunked) prefill against the decode
        cache. Returns (logits [B,C,V], new cache).

        ``tokens`` [B,C] are the next C prompt tokens of each row;
        ``positions`` [B,C] their absolute positions; ``write_pos``
        [B,C] the cache positions the K/V scatter to (the engine's drop
        sentinel for pad lanes / rows not advancing). Unlike
        ``decode_step`` the cache ``len`` vector does NOT advance — the
        prefill cursor is engine-owned state, and the decode scan that
        shares the dispatch still reads ``len`` for its own rows. The
        block table (``pages``) passes through untouched as in decode.
        Attention archs only (blocks.block_prefill_chunk raises on
        mamba); the engine gates accordingly.
        """
        cfg = self.cfg
        pages = cache.get("pages")
        x = embed(params["embed"], tokens).astype(cfg.dtype)
        if getattr(cfg, "scale_embeddings", False):
            x = x * jnp.sqrt(jnp.asarray(cfg.d_model, x.dtype))
        x = constrain_batch(x)

        def layer_fn(pp, x, lc, kind, use_moe, layer):
            return blocks.block_prefill_chunk(
                pp, x, lc, cfg, kind, use_moe, positions, write_pos,
                pages=pages, layer=layer)

        x, new_period_caches = self._scan_layers(params, cache, x, layer_fn)

        x, new_leftover = self._leftover_layers(params, cache, x, layer_fn)

        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = self._unembed(params, x)                # [B, C, V]
        new_cache = {"periods": new_period_caches, "leftover": new_leftover,
                     "len": cache["len"]}
        if pages is not None:
            new_cache["pages"] = pages
        return logits, new_cache

    def decode_step(self, params, cache, token_or_embed, *,
                    attn_impl: str = "gather"
                    ) -> Tuple[jax.Array, PyTree]:
        """One decode step. Returns (logits [B,V], new cache).

        When the cache carries a ``"pages"`` block table ([B, P] int32,
        from serve/kv_pages.PagedSlotPool) the attention layers run the
        paged decode path — ``attn_impl`` picks gather-then-attend (the
        executable reference) or the fused one-pass Pallas block-table
        kernel (kernels/paged_attention); the table itself is
        engine-owned and passes through unchanged. ``attn_impl`` is a
        trace-time constant: callers jitting this function pass a fixed
        Python string per compiled entry.
        """
        cfg = self.cfg
        cache_len = cache["len"]
        pages = cache.get("pages")
        if cfg.frontend is None:
            x = embed(params["embed"], token_or_embed[:, None]).astype(cfg.dtype)
            if getattr(cfg, "scale_embeddings", False):
                x = x * jnp.sqrt(jnp.asarray(cfg.d_model, x.dtype))
        else:
            x = token_or_embed.astype(cfg.dtype)
            if x.ndim == 2:
                x = x[:, None, :]
        x = constrain_batch(x)

        def layer_fn(pp, x, lc, kind, use_moe, layer):
            return blocks.block_decode(
                pp, x, lc, cache_len, cfg, kind, use_moe, pages=pages,
                attn_impl=attn_impl, layer=layer)

        x, new_period_caches = self._scan_layers(params, cache, x, layer_fn)

        x, new_leftover = self._leftover_layers(params, cache, x, layer_fn)

        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = self._unembed(params, x)[:, 0]
        new_cache = {"periods": new_period_caches, "leftover": new_leftover,
                     "len": cache_len + 1}
        if pages is not None:
            new_cache["pages"] = pages
        return logits, new_cache
