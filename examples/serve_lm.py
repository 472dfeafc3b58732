"""Slot-pool continuous batching example: N > K requests, FIFO-verified.

Round-trips 12 concurrent requests through a 4-slot engine — the
Algorithm-5 sleeping semaphore gates admission, each round admits the
FIFO prefix of the queue that the free slots hold, and one fixed-shape
batched decode serves all active slots per dispatch.

    PYTHONPATH=src python examples/serve_lm.py
    PYTHONPATH=src python examples/serve_lm.py --kv-layout paged

With ``--kv-layout paged`` the engine runs on the block-table page arena
(serve/kv_pages.py) and, after the round-trip, proves the layout's
point: at *equal arena bytes* it serves one context longer than the
contiguous layout's ``max_len``, with tokens identical to the legacy
per-request loop — and then the copy-on-write demo: two requests with
the *same prompt* served with ``--prefix-sharing on`` allocate fewer
total pages than with it off (the second request adopts the first's
prefix pages read-only and splits only at its first divergent write),
while emitting bit-identical token streams either way. DESIGN.md §11.
"""

import argparse

import numpy as np

from repro.launch.serve import main

DEFAULTS = ["--arch", "qwen3-14b", "--smoke", "--requests", "12",
            "--capacity", "4", "--prompt-len", "16", "--new-tokens", "8",
            "--legacy"]

if __name__ == "__main__":
    # only the layout knobs are overridable — the asserts below pin the
    # fixed 12-request workload
    ap = argparse.ArgumentParser()
    ap.add_argument("--kv-layout", default="slots",
                    choices=("slots", "paged"))
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--page-growth", default="lazy",
                    choices=("lazy", "eager"))
    ap.add_argument("--prefix-sharing", default="auto",
                    choices=("auto", "on", "off"))
    ex = ap.parse_args()
    argv = DEFAULTS + ["--kv-layout", ex.kv_layout,
                       "--page-size", str(ex.page_size),
                       "--page-growth", ex.page_growth,
                       "--prefix-sharing", ex.prefix_sharing]
    engine = main(argv)
    # N > K round-trip: every request finished, grants in arrival order
    assert len(engine.finished) == 12
    assert engine.grant_log == sorted(engine.grant_log), engine.grant_log
    assert all(len(r.out_tokens) == 8 for r in engine.finished)
    print("[example] 12 requests over 4 slots: FIFO grant order verified")

    if engine.kv_layout == "paged":
        import jax.numpy as jnp

        from repro.serve.engine import ServeEngine, SlotServeEngine

        engine.pool.check()                    # no page leaks after drain
        # Same arena bytes as the contiguous layout (K * max_len tokens),
        # one request almost twice as long as a slot row.
        max_len = engine.max_len
        long_len = 2 * max_len - 6
        prompt = np.asarray(
            np.random.default_rng(7).integers(1, 100, 12), np.int32)
        new_tokens = long_len - prompt.size
        paged = SlotServeEngine(
            engine.model, engine.params, capacity=4, max_len=max_len,
            kv_layout="paged", page_size=ex.page_size, decode_chunk=2,
            page_growth=ex.page_growth)
        req = paged.submit(prompt, max_new_tokens=new_tokens)
        paged.run_until_done(max_rounds=200)
        assert len(req.out_tokens) == new_tokens
        paged.pool.check()
        if ex.page_growth == "lazy":
            # the long context grew page by page: more allocation grants
            # than the single eager reservation, one lock acquire each
            assert paged.pool.pages.allocs > 1, "lazy growth never grew"
        legacy = ServeEngine(engine.model, engine.params, max_len=long_len + 1)
        want = legacy.generate(
            {"tokens": jnp.asarray(prompt)[None, :]}, new_tokens)
        assert req.out_tokens == np.asarray(want.tokens)[0].tolist()
        print(f"[example] paged arena served a {long_len}-token context "
              f"in a max_len={max_len} arena "
              f"(tokens match the legacy loop)")

        # --- copy-on-write prefix sharing: two same-prompt requests ---
        # The second request arrives after the first's prefill landed,
        # so admission finds the whole prompt in the prefix index and
        # adopts its pages (increfs, zero allocations for the prefix);
        # its first generated token write triggers exactly the CoW
        # split. Off re-allocates and re-scatters everything. The token
        # streams must agree bit-for-bit. The demo pins page_size=4 so
        # the 13-token prompt spans 3 full pages + a partial one: the
        # full pages are the net saving (the partial page's adoption is
        # repaid by the split copy — sharing pays off from the second
        # page of common prefix onward).
        demo_prompt = np.asarray(
            np.random.default_rng(11).integers(1, 100, 13), np.int32)

        def run_pair(mode):
            eng = SlotServeEngine(
                engine.model, engine.params, capacity=4, max_len=max_len,
                kv_layout="paged", page_size=4, decode_chunk=2,
                page_growth=ex.page_growth, prefix_sharing=mode)
            first = eng.submit(demo_prompt, max_new_tokens=6)
            eng.step()                       # leader inserts + decodes
            second = eng.submit(demo_prompt.copy(), max_new_tokens=6)
            eng.run_until_done(max_rounds=100)
            eng.pool.check()                 # refcounts drained cleanly
            assert eng.pool.pages.in_use == 0
            return eng, first, second

        on, on_a, on_b = run_pair("on")
        off, off_a, off_b = run_pair("off")
        assert on_a.out_tokens == off_a.out_tokens
        assert on_b.out_tokens == off_b.out_tokens
        assert on.prefix_hits >= 1 and on.shared_pages_adopted >= 1
        assert on.pool.pages.pages_alloced < off.pool.pages.pages_alloced
        print(f"[example] prefix sharing: same-prompt pair allocated "
              f"{int(on.pool.pages.pages_alloced)} pages shared vs "
              f"{int(off.pool.pages.pages_alloced)} unshared "
              f"({int(on.shared_pages_adopted)} adopted, "
              f"{int(on.cow_splits)} CoW split(s)); "
              f"token streams identical")
