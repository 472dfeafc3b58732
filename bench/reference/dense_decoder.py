"""Plain reference for a pre-norm, attention-only dense decoder.

Written from the configuration file alone (it imports nothing of the
program and takes nothing the program made). Per layer::

    h = rmsnorm(x) * attn_norm
    q, k, v = h @ wq, h @ wk, h @ wv            # [S, heads, head_dim]
    q, k = rmsnorm(q) * q_norm, rmsnorm(k) * k_norm     (qk_norm only)
    q, k = rope(q), rope(k)       # halves rotated, theta^(-i/half)
    a = softmax(q k^T / sqrt(head_dim), causal) v    # query head h reads
    x = x + a @ wo                                   # kv head h // group
    h = rmsnorm(x) * mlp_norm
    x = x + (silu(h @ w_gate) * (h @ w_up)) @ w_down     (hidden_act silu)
    x = x + relu(h @ w_in)^2 @ w_out                     (hidden_act relu2)

then ``rmsnorm(x) * final_norm`` and the output head (the embedding
table's transpose when ``tie_word_embeddings``). Everything is float32
at ``Precision.HIGHEST``; the weights are the benchmark's own bf16
draw. :func:`logits` with ``fp8=True`` is the control: every matrix
product takes float8_e4m3 operands (per-channel weight scales, per-token
activation scales), the step below the configuration's bf16.

:func:`draw` makes the weights on the device from a key in one jitted
call; :func:`to_program` hands the same arrays to the system under test
in its parameter layout.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block (bounds the scores)


def shapes(c: Dict[str, Any]) -> Dict[str, tuple]:
    d, L = c["hidden_size"], c["num_hidden_layers"]
    hq, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // hq
    f, v = c["intermediate_size"], c["vocab_size"]
    s = {"embed": (v, d), "final_norm": (d,),
         "attn_norm": (L, d), "mlp_norm": (L, d),
         "wq": (L, d, hq, hd), "wk": (L, d, kv, hd), "wv": (L, d, kv, hd),
         "wo": (L, hq, hd, d)}
    if c.get("qk_norm"):
        s["q_norm"] = (L, hd)
        s["k_norm"] = (L, hd)
    if c["hidden_act"] == "silu":
        s.update(w_gate=(L, d, f), w_up=(L, d, f), w_down=(L, f, d))
    elif c["hidden_act"] == "relu2":
        s.update(w_in=(L, d, f), w_out=(L, f, d))
    else:
        raise ValueError(f"hidden_act {c['hidden_act']!r}")
    if not c["tie_word_embeddings"]:
        s["lm_head"] = (d, v)
    return s


def _fan_in(name: str, shape: tuple) -> int:
    if name == "embed":
        # token vectors of norm ~1: the residual stream is then carried
        # by the layers, and with a tied head a token's own logit does
        # not dominate (at std 1 every step would repeat its input)
        return shape[1]
    if name == "wo":
        return shape[1] * shape[2]    # heads x head_dim
    if name == "lm_head":
        return shape[0]
    return shape[1]                   # [L, in, ...]


def draw(c: Dict[str, Any], key) -> Dict[str, jax.Array]:
    """Seeded weights, on the device, in one jitted call: matrices
    normal with std 1/sqrt(fan_in) in bf16 (the embedding table
    1/sqrt(hidden_size)); norm scales 1 + N(0, 0.1^2)
    in float32 (so a norm applied wrongly shows). Stacked per-layer
    leaves are drawn one layer at a time (``lax.map``), so no whole
    float32 copy of a leaf ever exists."""
    sh = shapes(c)
    names = sorted(sh)

    def one(name, k):
        shape = sh[name]
        if name.endswith("norm"):
            return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        std = 1.0 / math.sqrt(_fan_in(name, shape))

        def leaf(kk, shp):
            return (jax.random.normal(kk, shp, jnp.float32) * std
                    ).astype(jnp.bfloat16)
        if name.startswith("w"):
            return jax.lax.map(lambda kk: leaf(kk, shape[1:]),
                               jax.random.split(k, shape[0]))
        return leaf(k, shape)

    @jax.jit
    def go(key):
        keys = jax.random.split(key, len(names))
        return {n: one(n, k) for n, k in zip(names, keys)}
    return go(key)


def to_program(c: Dict[str, Any], w: Dict[str, jax.Array]) -> Dict:
    """The same arrays in the program's parameter tree (``repro.models``
    LM: one scanned period of one attention layer)."""
    mixer = {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"]}
    if c.get("qk_norm"):
        mixer["q_norm"] = {"scale": w["q_norm"]}
        mixer["k_norm"] = {"scale": w["k_norm"]}
    if c["hidden_act"] == "silu":
        ffn = {"gate": w["w_gate"], "up": w["w_up"], "down": w["w_down"]}
    else:
        ffn = {"in": w["w_in"], "out": w["w_out"]}
    tree = {"periods": {"layer_0": {"ln1": {"scale": w["attn_norm"]},
                                    "mixer": mixer,
                                    "ln2": {"scale": w["mlp_norm"]},
                                    "ffn": ffn}},
            "final_norm": {"scale": w["final_norm"]},
            "embed": {"tokens": w["embed"]}}
    if not c["tie_word_embeddings"]:
        tree["lm_head"] = {"kernel": w["lm_head"]}
    return tree


# ----------------------------------------------------------------- forward
def _q8(x, axis):
    """Round to float8_e4m3 with one scale per slice along ``axis``
    (the reduction axis of the product it feeds), back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** -(jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv            # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _hidden(c, w, tokens, fp8: bool):
    """Final normed hidden states [S, d] for one sequence."""
    eps = c["rms_norm_eps"]
    hq, kv = c["num_attention_heads"], c["num_key_value_heads"]
    group = hq // kv
    S = tokens.shape[0]
    pos = jnp.arange(S)

    def mm(spec, a, b, a_axis, b_axis):
        if fp8:
            a, b = _q8(a, a_axis), _q8(b, b_axis)
        return jnp.einsum(spec, a, b, precision=HI)

    f32 = lambda a: a.astype(jnp.float32)
    emb = f32(w["embed"])
    if fp8:
        emb = _q8(emb, -1)
    x = emb[tokens]
    hd = w["wq"].shape[-1]

    layer_keys = [k for k in w if k not in ("embed", "final_norm",
                                            "lm_head")]

    def layer(x, lw):
        lw = {k: f32(v) for k, v in lw.items()}
        h = _rms(x, eps) * lw["attn_norm"]
        q = mm("sd,dhk->shk", h, lw["wq"], -1, 0)
        k = mm("sd,dhk->shk", h, lw["wk"], -1, 0)
        v = mm("sd,dhk->shk", h, lw["wv"], -1, 0)
        if "q_norm" in lw:
            q = _rms(q, eps) * lw["q_norm"]
            k = _rms(k, eps) * lw["k_norm"]
        q = _rope(q, pos, c["rope_theta"])
        k = _rope(k, pos, c["rope_theta"])
        kx = jnp.repeat(k, group, axis=1)           # kv head h // group
        vx = jnp.repeat(v, group, axis=1)
        scale = 1.0 / math.sqrt(hd)

        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
            s = mm("qhk,thk->hqt", qb, kx, -1, -1) * scale
            qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s,
                          -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return mm("hqt,thk->qhk", p, vx, -1, 0)
        a = jax.lax.map(block, jnp.arange(S // Q_BLOCK))
        a = a.reshape(S, hq, hd)
        x = x + mm("shk,hkd->sd", a, lw["wo"], (-2, -1), (0, 1))
        h = _rms(x, eps) * lw["mlp_norm"]
        if "w_gate" in lw:
            g = mm("sd,df->sf", h, lw["w_gate"], -1, 0)
            u = mm("sd,df->sf", h, lw["w_up"], -1, 0)
            y = mm("sf,fd->sd", jax.nn.silu(g) * u, lw["w_down"], -1, 0)
        else:
            g = jnp.square(jax.nn.relu(mm("sd,df->sf", h, lw["w_in"], -1, 0)))
            y = mm("sf,fd->sd", g, lw["w_out"], -1, 0)
        return x + y, None

    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in layer_keys})
    return _rms(x, eps) * f32(w["final_norm"])


def logits(c, w, tokens, rows, fp8: bool = False):
    """Reference logits [len(rows), vocab] at positions ``rows`` of the
    sequence ``tokens`` (length a multiple of ``Q_BLOCK``; positions
    past the real sequence are padding that no real row attends to).
    The output head is applied a vocabulary block at a time, so no whole
    float32 copy of it exists."""
    x = _hidden(c, w, tokens, fp8)[rows]
    tied = c["tie_word_embeddings"]
    head = w["embed"] if tied else w["lm_head"]
    V, d = c["vocab_size"], c["hidden_size"]
    n = 1
    while V % n or (V // n) * d * 4 > 2 ** 29:
        n += 1
    blk = V // n
    if fp8:
        x = _q8(x, -1)

    def block(i):
        h = jax.lax.dynamic_slice_in_dim(head, i * blk, blk,
                                         0 if tied else 1)
        h = h.astype(jnp.float32)
        if fp8:
            h = _q8(h, -1 if tied else 0)
        spec = "rd,vd->rv" if tied else "rd,dv->rv"
        return jnp.einsum(spec, x, h, precision=HI)
    out = jax.lax.map(block, jnp.arange(n))              # [n, R, blk]
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)


def padded_len(n: int) -> int:
    return -(-n // Q_BLOCK) * Q_BLOCK


def gaps(c, w, tokens, rows, served, fp8_rows: Optional[bool] = False):
    """Per row: the served token's gap below the reference's best logit,
    over the row's largest |logit|; with ``fp8_rows`` also the gap of
    the token the fp8 control puts first (the control's reading)."""
    ref = logits(c, w, tokens, rows)
    top = ref.max(axis=-1)
    scale = jnp.abs(ref).max(axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    out = {"served": (top - got) / scale}
    if fp8_rows:
        ctl = logits(c, w, tokens, rows, fp8=True).argmax(axis=-1)
        pick = jnp.take_along_axis(ref, ctl[:, None], axis=-1)[:, 0]
        out["control"] = (top - pick) / scale
    return out
