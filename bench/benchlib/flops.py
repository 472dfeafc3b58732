"""Operations and bytes a dense decoder needs, computed from its shapes.

Counts are of useful work only: tokens that are really computed (no pad
lanes, dead rows or cached prompt tokens) and the bytes that work must
move at the least (weights once per sequential step, the live keys and
values each row attends to, the keys and values written). A time bound
built from them is therefore a lower bound on the device time, and a
share of it cannot pass 100% unless the counting is wrong.
"""

from __future__ import annotations

from typing import Any, Dict


def dims(c: Dict[str, Any]) -> Dict[str, int]:
    d = c["hidden_size"]
    hq, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // hq
    f, v, n = c["intermediate_size"], c["vocab_size"], c["num_hidden_layers"]
    gated = c["hidden_act"] == "silu"
    attn = d * hq * hd * 2 + d * kv * hd * 2          # wq, wo, wk, wv
    mlp = d * f * (3 if gated else 2)
    bytes_per = 2 if c.get("torch_dtype", "bfloat16") == "bfloat16" else 4
    return {"d": d, "hq": hq, "kv": kv, "hd": hd, "f": f, "v": v, "layers": n,
            "layer_params": attn + mlp, "head_params": d * v,
            "bytes_per_param": bytes_per,
            # keys and values of one token across all layers
            "kv_bytes_per_token": 2 * n * kv * hd * bytes_per}


def weight_bytes(c: Dict[str, Any]) -> int:
    """Matrices one forward step reads: every layer and the output head
    (the embedding table is gathered a row per token, not read whole)."""
    m = dims(c)
    return (m["layers"] * m["layer_params"] + m["head_params"]) \
        * m["bytes_per_param"]


def token_flops(c: Dict[str, Any], context: float, logits: bool) -> float:
    """Forward operations for one token that attends to ``context``
    positions; ``logits`` adds the output head (needed for an output
    token or a prompt's last token, not for other prompt tokens)."""
    m = dims(c)
    f = 2.0 * m["layers"] * m["layer_params"]
    f += 4.0 * m["layers"] * context * m["hq"] * m["hd"]   # QK^T and AV
    if logits:
        f += 2.0 * m["head_params"]
    return f
