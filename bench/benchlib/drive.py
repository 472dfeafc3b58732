"""Build the served path, warm it up, and drive one measured window.

The window drives what users run: ``repro.launch.serve.make_engine``
builds the paged ``SlotServeEngine`` from deployment settings only
(layout, page size, arena, capacity, chunk, prefix cache, the longest
context a slot holds), and ``repro.serve.frontend.AsyncFrontend`` feeds
it; clients submit and consume ``StreamHandle`` streams. Implementation
choices (attention path, bucketed dispatch, sync backend, allocator
wait, decode chunk) stay at the program's defaults.

Everything here times with ``time.perf_counter`` on the host; a request
is timed from when it was due, not from when it was submitted.
"""

from __future__ import annotations

import asyncio
import dataclasses
import math
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import traffic as traffic_mod

TRACE_FROM = 0.3       # the traced slice starts this share into the window
TAIL_WAIT_S = 60.0     # how long past the close a due request may take


@dataclasses.dataclass
class Req:
    due_s: float
    prompt: np.ndarray
    max_new: int
    submit_s: float = math.nan
    first_s: float = math.nan
    last_s: float = math.nan
    grant_s: float = math.nan
    n_out: int = 0
    n_before: int = 0    # tokens streamed before the window opened
    tokens: Optional[List[int]] = None
    state: str = "unsent"
    prefill_chunks: int = 0
    in_window: bool = False


@dataclasses.dataclass
class Round:
    t: float             # host clock at the round hook
    out_tokens: int      # tokens the round emitted (decode + first tokens)
    first_tokens: int    # of those, first tokens (sampled from a chunk)
    prefill_tokens: int  # prompt tokens the round's chunk computed
    ctx_decoding: int    # sum of decoding rows' contexts before the round
    n_decoding: int      # decoding rows before the round


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    reqs: List[Req]
    rounds: List[Round]
    stats_open: Dict[str, float]
    stats_close: Dict[str, float]
    tokens_in_window: int
    trace_t0: float = math.nan
    trace_t1: float = math.nan
    compiles_in_window: int = 0
    lateness_s: List[float] = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


# ------------------------------------------------------------- compiles
class CompileCounter:
    """Counts backend compilations (``jax.monitoring``), so the run can
    say whether anything compiled inside the window. JAX times a load
    from the persistent cache under the same event, so a warm run's
    warm-up counts its loads too."""

    def __init__(self, jax):
        self.n = 0

        def listen(name, *_a, **_k):
            if name.endswith("backend_compile_duration"):
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


# --------------------------------------------------------------- engine
def serve_args(serve, c: Dict[str, Any], seed: int):
    """The serving CLI's own parser, given deployment settings only.
    ``make_engine`` sizes a slot's block table from ``--prompt-len`` +
    ``--new-tokens`` + 1 (``max_len``; a slot holds up to twice that),
    so the two are set to cover the deployment's longest context."""
    dep = c["deployment"]
    need = dep["max_context"] + 2           # prompt + reply + slack token
    half = -(-need // (2 * dep["page_size"])) * dep["page_size"]
    argv = ["--arch", c["arch"], "--seed", str(seed % 2 ** 31),
            "--capacity", str(dep["capacity"]),
            "--prompt-len", str(half - 1), "--new-tokens", "0",
            "--kv-layout", "paged", "--page-size", str(dep["page_size"]),
            "--num-pages", str(dep["num_pages"]),
            "--prefill-chunk-tokens", str(dep["prefill_chunk_tokens"]),
            "--prefix-cache", dep["prefix_cache"]]
    return serve.build_parser().parse_args(argv)


def program_config(c: Dict[str, Any]):
    """The program's ``ArchConfig`` for this configuration file, checked
    against the file's sizes (a mismatch is a fault of the file)."""
    from repro.configs import get_arch
    cfg = dataclasses.replace(get_arch(c["arch"]),
                              **c.get("program_overrides", {}))
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    want = {"d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "resolved_head_dim": hd, "vocab_size": c["vocab_size"],
            "num_layers": c["num_hidden_layers"],
            "tie_embeddings": c["tie_word_embeddings"],
            "qk_norm": bool(c.get("qk_norm", False)),
            "rope_theta": float(c["rope_theta"]),
            "norm_eps": float(c["rms_norm_eps"]),
            "activation": {"silu": "swiglu"}.get(c["hidden_act"],
                                                 c["hidden_act"]),
            "param_dtype": c["torch_dtype"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise SystemExit(f"[bench] program config differs from "
                         f"{c['name']}.json (program, file): {bad}")
    return cfg


def build_engine(c, params, seed):
    from repro.launch import serve
    from repro.models import build_model
    model = build_model(program_config(c))
    args = serve_args(serve, c, seed)
    return serve.make_engine(model, params, args)


def warm_up(engine, vocab: int, seed: int) -> int:
    """Compile (or load from the cache) every program the window can
    dispatch, through the engine's public path only: for each occupancy
    from 1 to capacity, that many throw-away prompts of one prefill
    chunk, admitted together (a round with a chunk) and decoded to the
    end (rounds without one); then a queue longer than the planner's
    largest window. Drops what they left in the prefix cache, and
    returns the rounds it ran."""
    rng = np.random.default_rng([seed, 1])
    C = engine.prefill_chunk
    rounds = 0

    def serve_all(prompts, new):
        nonlocal rounds
        for p in prompts:
            engine.submit(p, new)
        while engine.queue or engine.active:
            engine.step()
            rounds += 1

    for k in range(1, engine.capacity + 1):
        serve_all(traffic_mod.warmup_prompts(rng, vocab, k, C), 4)
    serve_all(traffic_mod.warmup_prompts(
        rng, vocab, engine.plan_window + engine.capacity, 16), 2)
    engine.drop_prefix_cache()
    return rounds


# --------------------------------------------------------------- window
def drive(engine, sched, mix: Dict[str, Any], seconds: float, jax, *,
          trace_dir: Optional[str] = None,
          compiles: Optional[CompileCounter] = None,
          intake_limit: int = 1024) -> Window:
    """One measured window of the mix's traffic through the front-end."""
    return asyncio.run(_drive(engine, sched, mix, seconds, trace_dir, jax,
                              compiles, intake_limit))


async def _drive(engine, sched, mix, seconds, trace_dir, jax, compiles,
                 intake_limit) -> Window:
    from repro.serve.frontend import AsyncFrontend, IntakeFullError
    from repro.serve.engine import RequestState

    arr = mix["arrival"]
    backlog = arr["process"] == "backlog"
    clock = time.perf_counter
    reqs: List[Req] = []
    rounds: List[Round] = []
    st = {"open": None, "close": None, "stop": False, "tokens": 0,
          "trace_t0": math.nan, "trace_t1": math.nan, "tracing": False,
          "done_idx": len(engine.finished), "prev_ctx": (0, 0),
          "prev_pf": engine.prefill_tokens,
          "compiles_open": 0, "compiles_close": 0}
    opened = asyncio.Event()
    handles = {}

    annotate = jax.profiler.TraceAnnotation

    # the benchmark's own host spans around the calls into the program
    step = engine.step

    def traced_step():
        with annotate("round"):
            return step()
    engine.step = traced_step

    def decoding():
        n, ctx = 0, 0
        for r in engine.active.values():
            if r.state is RequestState.DECODING:
                n += 1
                ctx += int(r.prompt.size) + len(r.out_tokens)
        return n, ctx

    def open_window(now):
        st["open"] = now
        st["stats_open"] = engine.stats()
        st["compiles_open"] = compiles.n if compiles else 0
        opened.set()

    emitted: Dict[int, int] = {}      # id(ServeRequest) -> tokens seen

    async def hook(fe):
        now = clock()
        with annotate("hook"):
            fin = engine.finished[st["done_idx"]:]
            st["done_idx"] = len(engine.finished)
            live = list(engine.active.values())
            out = first = 0
            for r in live + fin:
                had = emitted.get(id(r), 0)
                out += len(r.out_tokens) - had
                first += int(had == 0 and len(r.out_tokens) > 0)
                emitted[id(r)] = len(r.out_tokens)
            for r in fin:
                emitted.pop(id(r), None)
            pf = engine.prefill_tokens
            n_dec, ctx = st["prev_ctx"]
            rounds.append(Round(
                t=now, out_tokens=out,
                first_tokens=first, prefill_tokens=pf - st["prev_pf"],
                ctx_decoding=ctx, n_decoding=n_dec))
            st["prev_pf"] = pf
            st["prev_ctx"] = decoding()
            if (backlog and st["open"] is None
                    and len(engine.active) == engine.capacity
                    and st["prev_ctx"][0] == engine.capacity):
                open_window(now)
            if (trace_dir and st["open"] is not None
                    and not st["tracing"] and st["close"] is None
                    and now >= st["open"] + TRACE_FROM * seconds):
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                st["window_span"] = jax.profiler.TraceAnnotation("window")
                st["window_span"].__enter__()
                st["tracing"], st["trace_t0"] = True, clock()

    async def client(req: Req, fe):
        with annotate("submit"):
            req.submit_s = clock()
            try:
                h = await fe.submit(req.prompt, req.max_new)
            except IntakeFullError:
                req.state = "shed"
                return
        handles[id(req)] = h
        req.state = "live"
        req.tokens = []
        try:
            async for tok in h:
                now = clock()
                with annotate("client"):
                    if req.n_out == 0:
                        req.first_s = now
                    req.last_s = now
                    req.n_out += 1
                    req.tokens.append(tok)
                    if st["open"] is None or now < st["open"]:
                        req.n_before += 1
                    elif st["close"] is None:
                        st["tokens"] += 1
        finally:
            # also for a stream cut at the close (its task cancelled)
            req.state = h.state.value
            if h.req is not None:
                req.grant_s = h.req.grant_s
                req.prefill_chunks = h.req.prefill_chunks

    async def session(s, fe, t0):
        history = ([sched.system_prompts[s.system]]
                   if s.system is not None else [])
        due = t0 + s.arrival_s
        for turn, (user, n_new) in enumerate(zip(s.users, s.replies)):
            if turn:
                due = reqs_of[s.sid][-1].last_s + s.think_s[turn - 1]
                if not math.isfinite(due):
                    return              # the previous turn never finished
            wait = due - clock()
            if wait > 0:
                await asyncio.sleep(wait)
            if st["stop"]:
                return
            prompt = np.concatenate(history + [user]).astype(np.int32)
            req = Req(due_s=due, prompt=prompt, max_new=n_new)
            reqs.append(req)
            reqs_of.setdefault(s.sid, []).append(req)
            await client(req, fe)
            if req.n_out < n_new:
                return                  # cut short (window over, or shed)
            history += [user, np.asarray(req.tokens, np.int32)]

    reqs_of: Dict[int, List[Req]] = {}
    async with AsyncFrontend(engine, intake_limit=intake_limit,
                             round_hook=hook) as fe:
        t0 = clock()
        tasks = [asyncio.ensure_future(session(s, fe, t0))
                 for s in sched.sessions]
        if not backlog:
            await asyncio.sleep(max(t0 + arr.get("preroll_s", 0.0) - clock(),
                                    0))
            open_window(clock())
        await opened.wait()
        await asyncio.sleep(max(st["open"] + seconds - clock(), 0))
        st["close"] = clock()
        st["stats_close"] = engine.stats()
        st["compiles_close"] = compiles.n if compiles else 0
        if st["tracing"]:
            st["trace_t1"] = clock()
            st["window_span"].__exit__(None, None, None)
            jax.profiler.stop_trace()
        if not backlog:
            # due in the window: wait for each, a while past the close
            limit = st["close"] + TAIL_WAIT_S
            while clock() < limit and any(
                    r.state in ("unsent", "live")
                    for r in reqs if st["open"] <= r.due_s < st["close"]):
                await asyncio.sleep(0.05)
        st["stop"] = True
        for h in handles.values():
            h.cancel()
        for t in tasks:
            if not t.done():
                t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    engine.step = step

    t_open, t_close = st["open"], st["close"]
    for r in reqs:
        r.in_window = (t_open <= r.due_s < t_close) if not backlog else (
            math.isfinite(r.grant_s) and r.grant_s < t_close)
    return Window(
        t_open=t_open, t_close=t_close, reqs=reqs, rounds=rounds,
        stats_open=st["stats_open"], stats_close=st["stats_close"],
        tokens_in_window=st["tokens"],
        trace_t0=st["trace_t0"], trace_t1=st["trace_t1"],
        compiles_in_window=st["compiles_close"] - st["compiles_open"],
        lateness_s=[r.submit_s - r.due_s for r in reqs
                    if math.isfinite(r.submit_s)
                    and t_open <= r.due_s < t_close])
