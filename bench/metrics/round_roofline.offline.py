"""Round program: the least time the traced rounds' useful work needs
(per round the larger of operations / peak bf16 rate and least bytes /
HBM bandwidth), over the device time of the round programs in the
trace. PERF.md states which bound applies."""


def read(run):
    t = run.trace
    rounds = run.traced_rounds()
    if not t or not rounds or t.get("round_device_s", 0) <= 0:
        return None
    pk = run.peaks
    bound = 0.0
    for r in rounds:
        w = run.round_work(r)
        bound += max(w["flops"] / pk["bf16_flops"],
                     w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * bound / t["round_device_s"] if bound > 0 else None
