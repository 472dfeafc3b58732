"""Attention read path: the least time the traced rounds' decode
attention needs (each decode token reads its row's live keys and values
once: decode tokens x mean context x key and value bytes per token, at
the HBM bandwidth), over the device self time of the ops in the
program's ``paged_attention`` name scope."""

from benchlib import flops


def read(run):
    t = run.trace or {}
    rounds = run.traced_rounds()
    if not rounds or t.get("scoped_device_s", 0) <= 0:
        return None
    per_token = flops.dims(run.config)["kv_bytes_per_token"]
    read_bytes = sum((r.out_tokens - r.first_tokens) * r.ctx_decoding
                     / r.n_decoding * per_token
                     for r in rounds if r.n_decoding)
    if read_bytes <= 0:
        return None
    return (100.0 * read_bytes / run.peaks["hbm_bytes_per_s"]
            / t["scoped_device_s"])
