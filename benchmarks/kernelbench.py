"""Pallas kernel micro-benchmarks (interpret-mode semantics + wall time).

Interpret-mode wall times are Python-evaluator times, NOT hardware times —
they are recorded to track kernel-logic regressions, and each row also
re-validates the kernel against its pure-jnp oracle.

Each kernel family emits one machine-readable record (family, config,
wall time, oracle match); ``main()`` keeps the legacy CSV lines for
``benchmarks.run``, and running this module directly also writes the
records to ``BENCH_kernels.json`` — the CI artifact the kernel gate
reads (every record's ``match`` must be true).
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.membench.ops import make_buffer, membench
from repro.launch.compile_cache import enable_compile_cache
from repro.kernels.membench.ref import membench_ref
from repro.kernels.paged_attention.kernel import fused_paged_decode
from repro.kernels.paged_attention.ref import paged_decode_ref
from repro.kernels.semaphore.ops import semaphore_admission
from repro.kernels.semaphore.ref import sleeping_semaphore_ref
from repro.kernels.ticket_lock.ops import ticket_lock_run
from repro.kernels.ticket_lock.ref import ticket_lock_ref
from repro.kernels.xf_barrier.ops import fresh_flags, xf_barrier
from repro.kernels.xf_barrier.ref import xf_barrier_ref

Record = Dict[str, object]


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) * 1e6


def bench_xf_barrier(n: int = 64) -> Record:
    ones = jnp.ones(n, jnp.int32)
    k, us = _timed(lambda: xf_barrier(fresh_flags(n), jnp.int32(1),
                                      ones, ones))
    r = xf_barrier_ref(fresh_flags(n), jnp.int32(1), ones, ones)
    ok = all(np.array_equal(np.asarray(a), np.asarray(b))
             for a, b in zip(k, r))
    return {"family": "xf_barrier", "name": f"kernel_xf_barrier_n{n}",
            "n": n, "us": us, "match": bool(ok)}


def bench_ticket_lock(n: int = 64) -> Record:
    key = jax.random.PRNGKey(0)
    arr = jax.random.permutation(key, jnp.arange(n, dtype=jnp.int32))
    m = jax.random.uniform(key, (n,), minval=0.5, maxval=1.5)
    b = jax.random.normal(key, (n,))
    (g1, t1, a1), us = _timed(lambda: ticket_lock_run(arr, m, b))
    g2, t2, a2 = ticket_lock_ref(arr, m, b)
    ok = (np.array_equal(np.asarray(g1), np.asarray(g2))
          and abs(float(a1) - float(a2)) < 1e-3)
    return {"family": "ticket_lock", "name": f"kernel_ticket_lock_n{n}",
            "n": n, "us": us, "match": bool(ok), "fifo": True}


def bench_semaphore(n: int = 64, capacity: int = 4) -> Record:
    key = jax.random.PRNGKey(0)
    at = jnp.sort(jax.random.uniform(key, (n,)) * 10)
    hold = jax.random.uniform(key, (n,), minval=0.1, maxval=2.0)
    (gk, rk, wk), us = _timed(
        lambda: semaphore_admission(at, hold, capacity=capacity))
    gr, rr, wr = sleeping_semaphore_ref(at, hold, capacity)
    ok = np.allclose(np.asarray(gk), np.asarray(gr), rtol=1e-6)
    return {"family": "semaphore",
            "name": f"kernel_semaphore_n{n}_k{capacity}",
            "n": n, "capacity": capacity, "us": us, "match": bool(ok)}


def bench_membench() -> List[Record]:
    out = []
    for cont in (True, False):
        for wr in (True, False):
            buf = make_buffer(16)
            (bk, sk), us = _timed(
                lambda: membench(buf, n_steps=16, contentious=cont,
                                 write=wr, repeats=8))
            br, sr = membench_ref(buf, 16, contentious=cont, write=wr,
                                  repeats=8)
            ok = np.allclose(np.asarray(bk), np.asarray(br))
            tag = f"{'c' if cont else 'n'}{'w' if wr else 'r'}"
            out.append({"family": "membench",
                        "name": f"kernel_membench_{tag}",
                        "contentious": cont, "write": wr,
                        "us": us, "match": bool(ok)})
    return out


def bench_paged_attention() -> List[Record]:
    """The fused paged-decode kernel (DESIGN.md §16) against its
    pure-jnp oracle: a GQA cell and an MHA cell, both with ragged
    lengths, a sentinel-tail table, and one fully-masked row."""
    out = []
    for tag, kv, g, ps in (("gqa4", 2, 4, 4), ("mha", 4, 1, 8)):
        b, hd, num_pages, p_cap = 4, 16, 24, 4
        rng = np.random.default_rng(17)
        q = jnp.asarray(rng.standard_normal((b, kv, g, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((num_pages, ps, kv, hd)),
                        jnp.float32)
        v = jnp.asarray(rng.standard_normal((num_pages, ps, kv, hd)),
                        jnp.float32)
        lens = rng.integers(1, p_cap * ps + 1, size=b)
        pages = np.full((b, p_cap), num_pages, np.int32)
        for i in range(b - 1):               # last row stays fully masked
            need = -(-int(lens[i]) // ps)
            pages[i, :need] = rng.choice(num_pages, size=need,
                                         replace=False)
        pages_j = jnp.asarray(pages)
        lens_j = jnp.asarray(lens, jnp.int32)
        got, us = _timed(lambda: fused_paged_decode(
            q, k, v, pages_j, lens_j, interpret=True))
        want = paged_decode_ref(q, k, v, pages_j, lens_j)
        ok = bool(np.allclose(np.asarray(got), np.asarray(want),
                              atol=1e-5, rtol=1e-5))
        out.append({"family": "paged_attention",
                    "name": f"kernel_paged_attention_{tag}",
                    "batch": b, "kv_heads": kv, "gqa_group": g,
                    "head_dim": hd, "page_size": ps,
                    "num_pages": num_pages, "table_width": p_cap,
                    "us": us, "match": ok})
    return out


def records() -> List[Record]:
    out = [bench_xf_barrier(), bench_ticket_lock(), bench_semaphore()]
    out += bench_membench()
    out += bench_paged_attention()
    return out


def _legacy_line(r: Record) -> str:
    extra = ";fifo=1" if r.get("fifo") else ""
    return f"{r['name']},{r['us']:.1f},match={int(bool(r['match']))}{extra}"


def main() -> List[str]:
    """benchmarks.run entry point: legacy CSV lines."""
    return [_legacy_line(r) for r in records()]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_kernels.json",
                    help="machine-readable per-family records (the CI "
                         "kernel-gate artifact); '' skips the write")
    args = ap.parse_args()
    enable_compile_cache()
    recs = records()
    for r in recs:
        print(_legacy_line(r))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(recs, f, indent=2)
        print(f"# wrote {args.out}")
