"""One-chip smoke run of the paged serving engine at qwen3-14b's widths.

    python chip_smoke.py [--seed N]

Drives the serving main path once, through ``repro.launch.serve``'s own
builders, on one TPU: qwen3-14b at its published widths in bf16 with
random weights drawn from ``--seed``, only the depth cut. Sixteen
requests of 512 prompt tokens each (half of them sharing a 256-token
prefix) and 32 greedy new tokens run through the paged engine with
chunked prefill, prefix sharing and bucketed dispatch — once with the
gather read path, once with the fused Pallas paged-decode kernel. Every
phase is gated: each request finishes, nothing fails or retries, grants
stay FIFO, no page leaks, every emitted token is near the top of a
contiguous-path reference forward, and the fused kernel agrees with the
gather path on the engine's live arena. A control phase serves the same
prompts from an arena corrupted on purpose, and the reference check must
reject it. Any failure exits non-zero.

The last line of standard output is the JSON verdict
``{"ok": true, "device": {"platform", "kind", "count"}}``; it is
printed only when every gate passed. Without a TPU the script exits 2
before doing anything: there is no CPU fallback.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

ARCH = "qwen3-14b"
# Depth cut: 12 of 40 layers. The compile rehearsal's memory_analysis
# for one v5e (16 GB) puts the largest round program (8 rows, a
# 128-token prefill chunk) at 11.24 GiB: 8.83 GiB of weights, the
# 1.125 GiB page arena once (the round program takes the cache over and
# updates it in place), the chunk's logits and the temporaries.
NUM_LAYERS = 12
NUM_PAGES = 1536            # x 16 tokens x 12 layers: 1.125 GiB of KV
CAPACITY = 8
PROMPT_LEN = 512
SHARED_PREFIX = 256
NEW_TOKENS = 32
REQUESTS = 16
ARRIVAL_GAP_STEPS = 4       # staggered, so shared prompts find live donors

# Output check. bf16 keeps 8 significant bits (unit roundoff 2^-9). The
# engine (chunked prefill, paged decode) and the contiguous reference
# forward round the 5120-wide activations in a different order about
# ten times per layer, so after 12 layers their logits differ by a few
# percent of the logit scale (sqrt(120) * 2^-9 ~ 2%). An emitted greedy
# token must therefore score within 2^-4 (~6%) of the largest |logit|
# at its position below that position's reference maximum. On a v5e at
# seed 0 the sound streams' worst deficit is 0.017, a token drawn at
# random shows 0.97 on average, and the control phase's streams, served
# from a deliberately corrupted arena, reach 0.65.
LOGIT_TOL = 2.0 ** -4
# Fused vs gather. Both paths round scores, softmax weights and the
# output to bf16 at most twice each (relative 2^-8); with scores of
# order 1 after qk-norm the normwise difference is a few 2^-8. 2^-5
# leaves room for that, while a wrong page, head or mask moves the
# attention output by the order of its own norm. On a v5e at seed 0 the
# fused path differs from gather by 0.0025, and by 1.43 when it reads
# the wrong kv head (the control).
FUSED_TOL = 2.0 ** -5


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the random weights and the prompts")
    return ap.parse_args(argv)


def serve_args(serve, seed: int, impl: str):
    """The serving CLI's own arguments for this run."""
    return serve.build_parser().parse_args([
        "--arch", ARCH, "--num-layers", str(NUM_LAYERS),
        "--seed", str(seed), "--capacity", str(CAPACITY),
        "--requests", str(REQUESTS), "--prompt-len", str(PROMPT_LEN),
        "--new-tokens", str(NEW_TOKENS), "--kv-layout", "paged",
        "--page-size", "16", "--num-pages", str(NUM_PAGES),
        "--prefill-chunk-tokens", "128", "--prefix-sharing", "on",
        "--bucketed-dispatch", "on", "--attention-impl", impl])


def make_prompts(seed: int, vocab: int) -> list:
    """Even-numbered requests share one 256-token prefix."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, SHARED_PREFIX)
    prompts = []
    for i in range(REQUESTS):
        p = rng.integers(0, vocab, PROMPT_LEN)
        if i % 2 == 0:
            p[:SHARED_PREFIX] = shared
        prompts.append(p.astype(np.int32))
    return prompts


def read_paths(jax, jnp, cfg):
    """Both paged read paths over the last layer of a live arena, and,
    as a control, the fused path reading every query against the next
    kv head (the fault a wrong head index in the kernel would make)."""
    from repro.models.attention import paged_decode_attention

    @jax.jit
    def both(k, v, q, pages, lens):
        last = k.shape[0] - 1         # read by index, as the layer scan does
        fused, gather = (paged_decode_attention(
            None, cfg, q, k, v, pages, lens, window=None, impl=impl,
            layer=last) for impl in ("fused", "gather"))
        wrong_head = paged_decode_attention(
            None, cfg, q, jnp.roll(k[last], 1, axis=-2),
            jnp.roll(v[last], 1, axis=-2), pages, lens, window=None,
            impl="fused")
        return fused, gather, wrong_head
    return both


def fused_vs_gather(jax, jnp, cfg, both, engine, key):
    """Both paged read paths on the engine's live arena and block tables,
    for the rows that hold tokens. Returns the normwise relative
    difference, the largest elementwise one, the rows compared, and the
    control's normwise relative difference."""
    view = engine.pool.cache_view()
    layer = view["periods"]["layer_0"]
    rows = np.flatnonzero(np.asarray(view["len"]) > 0)
    q = jax.random.normal(
        key, (engine.capacity, 1, cfg.num_heads, cfg.resolved_head_dim),
        jnp.bfloat16)
    fused, gather, wrong = (np.asarray(x, np.float32)[rows] for x in both(
        layer["k"], layer["v"], q, view["pages"], view["len"]))
    norm = np.linalg.norm(gather)
    return (float(np.linalg.norm(fused - gather) / norm),
            float(np.abs(fused - gather).max()), len(rows),
            float(np.linalg.norm(wrong - gather) / norm))


def plant_fault(jax, jnp, engine):
    """The control's planted fault: every physical page of the arena
    takes the contents of the page before it, as if every block-table
    entry pointed one page off. Page accounting is left intact."""
    engine.pool.arena = jax.tree_util.tree_map(
        lambda leaf: jnp.roll(leaf, 1, axis=1), engine.pool.arena)


def run_phase(jax, jnp, serve, model, params, cfg, prompts, seed, impl,
              plant=False):
    """Serve every prompt through one engine; returns (engine, wall_s,
    fused-vs-gather comparison or None, whether a fault was planted).
    The fused phase compares the two read paths whenever occupancy
    reaches a new high, and keeps the comparison made at the highest.
    With ``plant``, the arena is corrupted once, when half the rows are
    live."""
    args = serve_args(serve, seed, impl)
    engine = serve.make_engine(model, params, args)
    arrivals = ARRIVAL_GAP_STEPS * np.arange(len(prompts))
    compared, occupancy, planted = None, 0, False
    both = read_paths(jax, jnp, cfg)
    nxt = 0
    t0 = time.perf_counter()
    while nxt < len(prompts) or engine.queue or engine.active:
        while nxt < len(prompts) and arrivals[nxt] <= engine.step_clock:
            engine.submit(prompts[nxt], args.new_tokens)
            nxt += 1
        if engine.step() == 0 and not engine.queue and nxt < len(prompts):
            engine.step_clock += 1
        if impl == "fused" and len(engine.active) > occupancy:
            occupancy = len(engine.active)
            compared = fused_vs_gather(jax, jnp, cfg, both, engine,
                                       jax.random.PRNGKey(seed + 1))
        if (plant and not planted
                and len(engine.active) >= engine.capacity // 2):
            plant_fault(jax, jnp, engine)
            planted = True
    jax.block_until_ready(engine.pool.arena)
    return engine, time.perf_counter() - t0, compared, planted


def reference_check(jax, jnp, model, params, requests):
    """Teacher-forced contiguous forward over prompt + output for each
    request: per emitted token, its deficit below the position's
    largest reference logit, over the position's largest |logit|.
    Returns the worst deficit, how many tokens are the reference
    argmax, and the mean deficit of the vocabulary's average logit (the
    deficit a token drawn at random would show)."""
    first = PROMPT_LEN - 1

    @jax.jit
    def deficits(params, tokens, out):
        logits = model.forward(params, {"tokens": tokens})[0][0, first:]
        logits = logits.astype(jnp.float32)
        top = logits.max(axis=-1)
        got = jnp.take_along_axis(logits, out[:, None], axis=-1)[:, 0]
        scale = jnp.abs(logits).max(axis=-1)
        return ((top - got) / scale, logits.argmax(axis=-1) == out,
                (top - logits.mean(axis=-1)) / scale)

    worst, agree, spread = 0.0, 0, []
    for req in requests:
        out = np.asarray(req.out_tokens, np.int32)
        tokens = np.concatenate([req.prompt, out[:-1]])[None, :]
        ratio, same, rand = deficits(params, jnp.asarray(tokens),
                                     jnp.asarray(out))
        worst = max(worst, float(np.max(ratio)))
        agree += int(np.sum(same))
        spread.append(np.asarray(rand))
    return worst, agree, float(np.mean(np.concatenate(spread)))


def gate_phase(name, engine, requests, worst, gates):
    st = engine.stats()
    gates.append((f"{name}: all {REQUESTS} requests FINISHED",
                  len(requests) == REQUESTS and all(
                      r.state.value == "finished"
                      and len(r.out_tokens) == NEW_TOKENS
                      for r in requests)))
    gates.append((f"{name}: failed == 0 and rounds_retried == 0",
                  st["failed"] == 0 and st["rounds_retried"] == 0))
    gates.append((f"{name}: FIFO grant order",
                  engine.grant_log == sorted(engine.grant_log)))
    gates.append((f"{name}: every token within {LOGIT_TOL} of the "
                  f"reference maximum (worst {worst:.5f})",
                  worst <= LOGIT_TOL))


def main(argv=None) -> int:
    opts = parse(argv)
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[smoke] device: {device}")
    if dev.platform != "tpu":
        print("[smoke] FAIL: no TPU; this check runs only on the chip",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    return run(opts, jax, jnp, dev, device)


def run(opts, jax, jnp, dev, device) -> int:
    """Every phase, in this one process; returns the exit code."""
    from repro import kernels
    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache
    from repro.sync import get_backend

    print(f"[smoke] compile cache: {enable_compile_cache()}")
    args = serve_args(serve, opts.seed, "gather")
    t0 = time.perf_counter()
    cfg, model, params = serve.build(args)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"[smoke] model: {cfg.name} at published widths (d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, "
          f"head_dim {cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, qk_norm {cfg.qk_norm}, {cfg.param_dtype}); "
          f"depth cut to {cfg.num_layers} of 40 layers so weights and "
          f"arena fit one chip's 16 GB; {n_params / 1e9:.2f}B params "
          f"drawn from seed {opts.seed} in "
          f"{time.perf_counter() - t0:.1f}s")

    gates = []
    planner = get_backend(serve.make_sync_library(args)
                          .planning_backend_name())
    print(f"[smoke] admission planner: {planner.name}[{planner.tier}]")
    gates.append(("admission planner runs the compiled kernel",
                  planner.tier == "compiled"))
    gates.append(("Pallas kernels compile (interpret off)",
                  not kernels.default_interpret()))

    prompts = make_prompts(opts.seed, cfg.vocab_size)
    streams = {}
    for impl in ("gather", "fused"):
        engine, wall, compared, _ = run_phase(
            jax, jnp, serve, model, params, cfg, prompts, opts.seed, impl)
        arena_bytes = sum(x.nbytes for x in
                          jax.tree_util.tree_leaves(engine.pool.arena))
        st = engine.stats()
        print(f"[smoke] {impl}: {int(st['finished'])} finished, "
              f"{int(st['tokens'])} tokens in {wall:.1f}s wall (host "
              f"clock, compiles included), "
              f"{int(st['decode_dispatches'])} rounds, "
              f"{int(st['prefix_hits'])} prefix hits, "
              f"{int(st['cow_splits'])} CoW splits, "
              f"{int(st['dispatch_trace_keys'])} traced shapes, "
              f"attention_kv_grouped {st['attention_kv_grouped']:.1f}, "
              f"arena_in_place {st['arena_in_place']:.1f}; page "
              f"arena {engine.pool.pages.num_pages} pages = "
              f"{arena_bytes} bytes")
        serve.enforce_leak_gate(engine)     # exits non-zero on a leak
        requests = sorted(engine.finished, key=lambda r: r.rid)
        worst, agree, spread = reference_check(jax, jnp, model, params,
                                               requests)
        print(f"[smoke] {impl}: reference check worst deficit "
              f"{worst} of max|logit| (tolerance {LOGIT_TOL}); "
              f"{agree}/{REQUESTS * NEW_TOKENS} tokens are the "
              f"reference argmax; a random token's mean deficit {spread}")
        gate_phase(impl, engine, requests, worst, gates)
        if compared is not None:
            rel, max_abs, n_rows, wrong = compared
            print(f"[smoke] fused vs gather on the live arena "
                  f"({n_rows} rows): normwise relative {rel}, max "
                  f"abs {max_abs} (tolerance {FUSED_TOL}); control with "
                  f"the kv heads rolled by one: normwise relative {wrong}")
            gates.append(("fused kernel matches gather on the live arena",
                          rel <= FUSED_TOL))
            gates.append(("control: a wrong kv head exceeds the fused "
                          "tolerance", wrong > FUSED_TOL))
        elif impl == "fused":
            gates.append(("fused vs gather compared on the live arena",
                          False))
        streams[impl] = [list(r.out_tokens) for r in requests]
        del engine
        gc.collect()

    # Control: the same gate must reject a stream served from a corrupted
    # arena, or passing it shows nothing.
    engine, wall, _, planted = run_phase(
        jax, jnp, serve, model, params, cfg, prompts, opts.seed, "gather",
        plant=True)
    serve.enforce_leak_gate(engine)
    requests = sorted(engine.finished, key=lambda r: r.rid)
    worst, agree, _ = reference_check(jax, jnp, model, params, requests)
    print(f"[smoke] control (arena pages rolled by one at half "
          f"occupancy): {len(requests)} finished in {wall:.1f}s wall; "
          f"reference check worst deficit {worst} of max|logit|; "
          f"{agree}/{sum(len(r.out_tokens) for r in requests)} tokens are "
          f"the reference argmax")
    gates.append((f"control: the reference check rejects a corrupted "
                  f"arena (worst {worst:.5f} > {LOGIT_TOL})",
                  planted and worst > LOGIT_TOL))
    del engine
    gc.collect()

    same = sum(a == b for ga, fa in zip(streams["gather"], streams["fused"])
               for a, b in zip(ga, fa))
    print(f"[smoke] token agreement gather vs fused: "
          f"{same}/{REQUESTS * NEW_TOKENS}")
    stats = dev.memory_stats() or {}
    print(f"[smoke] peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"of bytes_limit {stats.get('bytes_limit')}")

    failed = [name for name, ok in gates if not ok]
    for name, ok in gates:
        print(f"[smoke] {'PASS' if ok else 'FAIL'}: {name}")
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
