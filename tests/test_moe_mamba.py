"""MoE routing invariants + Mamba forward/decode consistency."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:  # hypothesis is optional in this image (tests/_hypothesis_compat.py)
    from hypothesis import example, given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import example, given, settings, st

from repro.configs import get_arch
from repro.configs.base import ArchConfig, MoEConfig, SSMConfig
from repro.models import moe as moe_mod
from repro.models.common import init_params
from repro.models.mamba import (mamba_decode_step, mamba_forward,
                                mamba_spec, mamba_state_shape)


def _moe_cfg(e=4, k=2, cf=1.25):
    return ArchConfig(
        name="t", family="moe", num_layers=1, d_model=16, num_heads=2,
        num_kv_heads=2, d_ff=32, vocab_size=64,
        moe=MoEConfig(num_experts=e, top_k=k, capacity_factor=cf))


def _aux_loss_reference(x, router, e, k):
    """Switch/GShard balance loss, E/k * sum_e mean_prob_e * load_e, in
    numpy from the router logits alone."""
    logits = np.einsum("bsd,de->bse", np.asarray(x, np.float64),
                       np.asarray(router, np.float64))
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = np.argsort(-probs, axis=-1)[..., :k]
    load = np.stack([(top == j).any(-1).mean() for j in range(e)])
    return e * float(np.sum(probs.mean(axis=(0, 1)) * load)) / k


@settings(max_examples=8, deadline=None)
@given(e=st.sampled_from([2, 4, 8]), k=st.integers(1, 2),
       seed=st.integers(0, 1000))
@example(e=4, k=2, seed=36)     # aux 0.988: balance is not >= 1 per draw
def test_moe_route_invariants(e, k, seed):
    cfg = _moe_cfg(e=e, k=min(k, e))
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (2, 16, cfg.d_model))
    router = jax.random.normal(jax.random.PRNGKey(seed + 1),
                               (cfg.d_model, e))
    cap = moe_mod.capacity(16, e, cfg.moe.top_k, cfg.moe.capacity_factor)
    dispatch, combine, aux = moe_mod.route(x, router, e, cfg.moe.top_k, cap)
    d = np.asarray(dispatch)
    c = np.asarray(combine)
    # each (expert, slot) holds at most one token per batch row
    assert np.all(d.sum(axis=1) <= 1.0 + 1e-5)
    # each token occupies at most top_k slots
    assert np.all(d.sum(axis=(2, 3)) <= cfg.moe.top_k + 1e-5)
    # combine weights are a sub-distribution per token
    assert np.all(c.sum(axis=(2, 3)) <= 1.0 + 1e-5)
    assert 0.0 <= float(aux["moe_drop_frac"]) <= 1.0
    # the balance loss is the Switch formula; each expert's load is at
    # most 1 and the mean router probabilities sum to 1, so it lies in
    # (0, E/k]
    loss = float(aux["moe_aux_loss"])
    np.testing.assert_allclose(
        loss, _aux_loss_reference(x, router, e, cfg.moe.top_k), rtol=1e-4)
    assert 0.0 < loss <= e / cfg.moe.top_k + 1e-5


def test_moe_no_drops_with_huge_capacity():
    cfg = _moe_cfg(e=4, k=2, cf=8.0)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 16, cfg.d_model))
    router = jax.random.normal(jax.random.PRNGKey(1), (cfg.d_model, 4))
    cap = moe_mod.capacity(16, 4, 2, 8.0)
    _, _, aux = moe_mod.route(x, router, 4, 2, cap)
    assert float(aux["moe_drop_frac"]) == 0.0


def test_moe_ffn_shapes_and_finite():
    cfg = _moe_cfg()
    p = init_params(moe_mod.moe_spec(cfg, jnp.float32), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y, aux = moe_mod.moe_ffn(p, x, cfg)
    assert y.shape == x.shape
    assert np.all(np.isfinite(np.asarray(y)))


# --------------------------------------------------------------------- mamba
def _ssm_cfg():
    return ArchConfig(
        name="t", family="ssm", num_layers=1, d_model=24, num_heads=0,
        num_kv_heads=0, d_ff=0, vocab_size=64,
        layer_pattern=("mamba",),
        ssm=SSMConfig(state_dim=4, conv_width=3, expand=2, dt_rank=8),
        param_dtype="float32")


def test_mamba_decode_matches_forward():
    """Stepping the recurrence token-by-token == the chunked train scan."""
    cfg = _ssm_cfg()
    p = init_params(mamba_spec(cfg, jnp.float32), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, cfg.d_model)) * 0.5

    y_full, final_state = mamba_forward(p, x, cfg, chunk=4)

    conv_shape, h_shape = mamba_state_shape(cfg, 2)
    state = (jnp.zeros(conv_shape, jnp.float32),
             jnp.zeros(h_shape, jnp.float32))
    ys = []
    for t in range(12):
        y_t, state = mamba_decode_step(p, x[:, t:t + 1], state, cfg)
        ys.append(y_t)
    y_step = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_full),
                               atol=2e-4, rtol=2e-3)
    # final hidden state of the forward pass matches the stepped state
    np.testing.assert_allclose(np.asarray(state[1]),
                               np.asarray(final_state[1]),
                               atol=2e-4, rtol=2e-3)


@pytest.mark.parametrize("chunk", [3, 4, 6, 12])
def test_mamba_chunk_invariance(chunk):
    """The chunked scan must be chunk-size invariant."""
    cfg = _ssm_cfg()
    p = init_params(mamba_spec(cfg, jnp.float32), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, cfg.d_model)) * 0.5
    y_ref, _ = mamba_forward(p, x, cfg, chunk=12)
    y, _ = mamba_forward(p, x, cfg, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-3)


def test_gather_dispatch_equals_einsum_dispatch():
    """§Perf iteration 5: the index/gather MoE dispatch must be numerically
    identical (fwd + grad) to the one-hot einsum dispatch."""
    for e, k, cf in [(4, 2, 1.25), (8, 4, 1.0), (16, 8, 1.25)]:
        cfg = _moe_cfg(e=e, k=k, cf=cf)
        p = init_params(moe_mod.moe_spec(cfg, jnp.float32),
                        jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
        y1, a1 = moe_mod.moe_ffn_einsum(p, x, cfg)
        y2, a2 = moe_mod.moe_ffn_gather(p, x, cfg)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   atol=2e-5, rtol=2e-5)
        g1 = jax.grad(lambda xx: moe_mod.moe_ffn_einsum(p, xx, cfg)[0].sum())(x)
        g2 = jax.grad(lambda xx: moe_mod.moe_ffn_gather(p, xx, cfg)[0].sum())(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=2e-4, rtol=2e-4)
        assert abs(float(a1["moe_drop_frac"]) - float(a2["moe_drop_frac"])) < 1e-6
