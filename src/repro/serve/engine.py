"""Serving engines: legacy per-request loop + slot-based continuous batching.

``ServeEngine`` is the original per-request Python decode loop (kept as
the baseline that ``benchmarks/servebench.py`` measures against and for
single-stream generation). ``SlotServeEngine`` is the production path:

  * a preallocated KV arena — either the contiguous ``[K, max_len, ...]``
    slot layout (serve/kv_slots.py) or, with ``kv_layout="paged"``, the
    block-table page arena (serve/kv_pages.py): same arena bytes, but a
    slot may grow past ``max_len`` while its neighbours are short, and
    page allocation/reclamation on this hot loop go through the sync
    library's ticket-lock mutex — K is the replica's concurrency budget;
  * one jitted fixed-shape batched ``decode_step`` over all K slots per
    iteration, with a ``lax.scan`` inner loop decoding ``decode_chunk``
    tokens per dispatch and finished/vacant rows masked (they still
    compute, at fixed shape, but their tokens are frozen and their cache
    writes drop once out of range);
  * admission gated by the paper's Algorithm-5 semaphore: the host
    ``AdmissionController`` (a live semaphore from the injected
    ``SyncLibrary`` — sleeping by default, spin via the library's
    ``semaphore_kind`` pin) grants every slot, and a round admits the
    FIFO prefix of the queue that the free slots can hold — exactly the
    queued requests an Algorithm-5 timeline over the in-flight holds
    grants with ``waited == 0`` *now*. FIFO grant order is the
    semaphore's fairness guarantee, and the engine records it in
    ``grant_log`` so callers can verify it.

All primitive access goes through the injected ``SyncLibrary`` (the
``sync`` constructor argument): the live gate's algorithm is
configuration — ``launch/serve.py`` exposes it as ``--admission-sem``.

The engine owns cache layout: models just read/write the arena row they
are handed (per-slot ``len`` vectors; models/blocks.block_decode).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import enum
import math
import time
from typing import Any, Deque, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.models import attention as attn
from repro.serve.dispatch import DecodeDispatchCache
from repro.serve.faults import FaultPlan, InjectedFault
from repro.serve.kv_pages import PagedSlotPool, PrefixIndex
from repro.serve.prefix_cache import PrefixCache, cache_key_suffix
from repro.serve.kv_slots import SlotPool
from repro.serve.scheduler import (AdmissionController,
                                   allocator_contention, plan_round)
from repro.sync import SyncLibrary

PyTree = Any


#: Write-drop sentinel for chunked prefill: pad lanes of a partial last
#: chunk (and rows not advancing this round) carry this as their cache
#: write position. Large and POSITIVE — past any block table (the paged
#: scatter maps it to the sentinel page) and past any contiguous row
#: (``mode="drop"``); a negative position would be *wrapped* into a
#: valid cell by JAX's index semantics, silently corrupting live KV.
_DROP_POS = 2 ** 30

#: Host spans the serving program records on the profiler's trace
#: (``jax.profiler``; DESIGN.md §17), one per stretch of host work in a
#: scheduler round: ``serve.*`` inside ``SlotServeEngine.step`` (the
#: round itself is ``serve.step``, a step annotation numbered by the
#: engine's round counter), ``frontend.*`` in ``AsyncFrontend``'s loop.
SPANS = ("serve.step", "serve.admit", "serve.grant", "serve.grow",
         "serve.dispatch", "serve.wait", "serve.harvest", "serve.retire",
         "frontend.pump", "frontend.intake")

#: Fraction of the page arena lazy-growth admission keeps free for
#: in-flight top-ups, and the default of ``cache_watermark``.
_ADMIT_HEADROOM = 0.1

#: Chunks ahead a lazy top-up covers (capped at the request's
#: admission-time bound), so a long decode pays one grow acquire per
#: window, not per chunk; one chunk when the pool is under the headroom.
_PAGE_LOOKAHEAD_CHUNKS = 2


class RequestState(str, enum.Enum):
    """Lifecycle of a request through the serving stack (DESIGN.md §13).

    ``QUEUED → PREFILLING → DECODING → FINISHED`` is the happy path;
    ``CANCELLED`` (client tore the stream down) and ``EXPIRED`` (the
    request's deadline passed while it could still be shed: in the
    queue, or as a page-pressure eviction victim once late) are the
    other terminal states. A lazy-growth preemption moves a request
    *back* to QUEUED — restart, not termination — unless it is already
    past its deadline, in which case eviction expires it instead of
    burning pages regenerating a stream that can no longer meet its
    SLO.

    ``FAILED`` (DESIGN.md §15) is the quarantine terminal: after
    ``quarantine_after`` consecutive round failures blamed on one
    request, the engine evicts just that request — its error surfaces
    on the caller's handle, its pages ride the normal deferred-free
    path, and the surviving rows' token streams stay bit-identical to
    a fault-free run.
    """
    QUEUED = "queued"
    PREFILLING = "prefilling"
    DECODING = "decoding"
    FINISHED = "finished"
    CANCELLED = "cancelled"
    EXPIRED = "expired"
    FAILED = "failed"

    @property
    def terminal(self) -> bool:
        return self in _TERMINAL_STATES


_TERMINAL_STATES = frozenset({RequestState.FINISHED,
                              RequestState.CANCELLED,
                              RequestState.EXPIRED,
                              RequestState.FAILED})


@dataclasses.dataclass
class GenerationResult:
    tokens: jnp.ndarray          # [B, n_generated]
    logprobs: Optional[jnp.ndarray] = None


class ServeEngine:
    """Legacy engine: wraps a model with jitted prefill/decode and a
    per-request Python sampling loop (no slot reuse, no admission)."""

    def __init__(self, model, params, *, max_len: int = 256,
                 temperature: float = 0.0):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.temperature = temperature
        self._decode = jax.jit(model.decode_step)

    def prefill(self, batch: Dict[str, jax.Array]) -> Tuple[jax.Array, PyTree]:
        if self.model.cfg.is_encdec:
            return self.model.prefill(self.params, batch)
        return self.model.prefill(self.params, batch, max_len=self.max_len)

    def _sample(self, logits: jax.Array, key) -> jax.Array:
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.temperature).astype(jnp.int32)

    def generate(self, batch: Dict[str, jax.Array], n_tokens: int,
                 key=None, eos_id: Optional[int] = None) -> GenerationResult:
        key = key if key is not None else jax.random.PRNGKey(0)
        logits, cache = self.prefill(batch)
        outs = []
        tok = self._sample(logits, key)
        outs.append(tok)
        done = jnp.zeros_like(tok, dtype=bool)
        for i in range(n_tokens - 1):
            key, sub = jax.random.split(key)
            logits, cache = self._decode(self.params, cache, tok)
            tok = self._sample(logits, sub)
            if eos_id is not None:
                done = done | (tok == eos_id)
                tok = jnp.where(done, eos_id, tok)
            outs.append(tok)
            if eos_id is not None and bool(jnp.all(done)):
                break
        return GenerationResult(tokens=jnp.stack(outs, axis=1))


# ---------------------------------------------------------------------------
# Slot-based continuous batching
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeRequest:
    """One request's lifecycle through the slot engine (all step-clock
    timestamps are in decode-step units; *_s are wall-clock seconds)."""
    rid: int
    prompt: np.ndarray                 # [L] int32 token ids
    max_new_tokens: int
    arrival_step: int = 0
    arrival_s: float = 0.0
    grant_step: int = -1
    grant_s: float = 0.0
    finish_step: int = -1
    finish_s: float = 0.0
    slot: int = -1
    eos: bool = False
    #: lifecycle state (DESIGN.md §13) — engine-owned; the async
    #: front-end only reads it
    state: RequestState = RequestState.QUEUED
    #: step-clock deadline (absolute): past it, a queued request is
    #: shed as EXPIRED and an active one becomes *late* — deprioritized
    #: for prefill-chunk grants and first in line for page-pressure
    #: eviction (which expires rather than requeues it). None = no SLO.
    deadline_step: Optional[int] = None
    #: wall-clock deadline (absolute ``time.perf_counter()`` seconds);
    #: same semantics as ``deadline_step``, either alone suffices
    deadline_s: Optional[float] = None
    #: step at which the request left PREFILLING for DECODING (the
    #: prefilling → decoding transition; == grant_step when prefill ran
    #: inside admission, i.e. one-shot mode)
    decode_start_step: int = -1
    #: times this request was evicted mid-stream by the lazy-growth
    #: overflow path and restarted from its prompt (greedy decoding makes
    #: the regenerated stream identical). Its original grant keeps the
    #: wait-time stats and the one FIFO grant-log entry.
    preemptions: int = 0
    #: chunked-prefill rounds this request's prompt consumed (0 when the
    #: engine prefilled it in one shot); cumulative across preemptions
    prefill_chunks: int = 0
    #: why the request FAILED (quarantine path, DESIGN.md §15); None for
    #: every other terminal state
    error: Optional[str] = None
    out_tokens: List[int] = dataclasses.field(default_factory=list)

    @property
    def wait_steps(self) -> int:
        return self.grant_step - self.arrival_step

    @property
    def wait_s(self) -> float:
        return self.grant_s - self.arrival_s

    # -------------------------------------------- time-in-state ledger
    # The three durations partition a granted request's lifetime:
    # queued + prefilling + decoding == finish_step - arrival_step.
    @property
    def queued_steps(self) -> int:
        """Steps spent QUEUED (== wait_steps for granted requests)."""
        end = self.grant_step if self.grant_step >= 0 else self.finish_step
        return max(end - self.arrival_step, 0)

    @property
    def prefill_steps(self) -> int:
        """Steps spent PREFILLING (0 in one-shot mode, where the whole
        prompt prefills inside the granting round)."""
        if self.grant_step < 0 or self.decode_start_step < 0:
            return 0
        return max(self.decode_start_step - self.grant_step, 0)

    @property
    def decode_steps(self) -> int:
        """Steps spent DECODING before reaching a terminal state."""
        if self.decode_start_step < 0 or self.finish_step < 0:
            return 0
        return max(self.finish_step - self.decode_start_step, 0)

    def past_deadline(self, step_clock: int,
                      now_s: Optional[float] = None) -> bool:
        """Whether either deadline has passed (strictly: a request AT
        its deadline step is still on time)."""
        if self.deadline_step is not None and step_clock > self.deadline_step:
            return True
        if self.deadline_s is not None:
            if (now_s if now_s is not None
                    else time.perf_counter()) > self.deadline_s:
                return True
        return False


class SlotServeEngine:
    """Continuous-batching engine over a fixed KV slot arena.

    Drive it with ``submit`` + ``run_until_done``, or ``step`` manually
    from an outer serving loop. Decoder-only token LMs only (the slot
    pool itself also handles encoder-decoder caches; wiring an encdec
    front-end is an open roadmap item).

    Under ``kv_layout="paged"`` allocator lock traffic is O(1) per
    engine event: admissions, top-ups, and retirements each take the
    page allocator's ticket mutex once *per scheduler round*, not per
    request or per page. ``page_growth`` picks the reservation policy:

      * ``"eager"`` — every page a request may ever touch is granted at
        insert (PR 3 semantics: decode never allocates mid-dispatch);
      * ``"lazy"`` (default) — insert grants only the prefill bucket and
        a per-round top-up pass covers each coming chunk, so short-lived
        requests never touch pages they won't fill; admission gates on
        a headroom watermark (``_ADMIT_HEADROOM``, the fraction of the
        arena kept free for in-flight top-ups) instead of the worst
        case, and the overflow path — pause the starved row for a round,
        preempt the youngest grant if *nobody* can decode — is
        eviction-safe: with greedy decoding both modes emit identical
        token streams and the engine ``grant_log`` stays the FIFO
        admission order.

    ``allocator_wait`` pins the allocator's wait strategy ("spin",
    "spin_backoff", "sleeping") or selects ``"adaptive"`` — re-resolved
    between rounds from the measured contended-acquire fraction.

    ``prefill_chunk_tokens`` (DESIGN.md §12) turns on *continuous
    chunked prefill*: admission becomes pure bookkeeping (slot + pages
    + a prefill cursor — no model dispatch), and each scheduler round's
    single jitted dispatch carries a C-token prefill sub-step for the
    FIFO-oldest prefilling slots alongside the decode scan.
    ``round_token_budget`` caps how much prefill a round carries
    (``scheduler.plan_round``: decode rows are funded first and never
    displaced; leftover budget funds chunks). The dispatch stays fixed
    shape with exactly two traces — ``chunk ∈ {0, C}`` — so rounds
    never retrace as the prefill/decode mix shifts, and chunking adds
    zero allocator acquires per round (chunk page demand folds into
    the existing top-up batch). Gated like lazy growth to greedy
    decoding + attention-only archs (silently off otherwise): greedy
    token streams are identical to one-shot prefill, and chunk
    partitioning cannot change results (each chunk scatters K/V into
    the cache *first*, then attends to the gathered view — the same
    computation whatever the chunk boundaries).

    ``prefix_sharing`` ("auto"/"on"/"off", DESIGN.md §11) adds
    copy-on-write prompt-prefix sharing on the paged layout: admission
    looks the new prompt up in a :class:`PrefixIndex` (longest live
    match at page granularity, same prefill bucket), adopts the matched
    pages read-only (an incref riding the admission batch's one
    allocator acquire) and scatters only the private remainder — a
    request repeating a live prompt allocates *zero* prefix pages. The
    per-round page-prep pass enforces the split invariant — *a shared
    page is never written; a written page has refcount 1* — by giving
    any slot whose next write targets a shared page a private copy
    (alloc + arena copy + decref, folded into the top-up pass's one
    acquire); a slot whose split is starved pauses with its block-table
    row sentinel-masked for the dispatch, so no dispatch ever writes a
    page another slot still reads. "auto" enables sharing exactly when
    its bit-identity contract is checkable: paged layout, greedy
    decoding, attention prefill (padded buckets). Token streams are
    bit-identical with sharing on or off.

    ``attention_impl`` (DESIGN.md §16) picks the paged decode read
    path: ``"gather"`` (gather-then-attend, the executable reference)
    or ``"fused"`` (one-pass Pallas block-table walk,
    kernels/paged_attention). Both produce logits within
    interpret-tier tolerance and bit-identical greedy token streams —
    the kernel-equivalence test tier (tests/test_paged_attention.py)
    and the CI servebench gate pin exactly that.

    ``bucketed_dispatch`` ("auto"/"on"/"off") layers a bucketed
    compiled-dispatch cache over scheduler rounds: instead of always
    dispatching the full ``[K]``-row round, the engine gathers the
    active slots into the smallest power-of-2 occupancy bucket
    (``serve.dispatch.DecodeDispatchCache``), dispatches that
    fixed shape, and scatters outputs back — so the jit cache holds at
    most ``log2(K)+1`` entries per ``chunk`` variant and rounds never
    retrace as occupancy shifts. Pad lanes are inert by construction:
    frozen, sentinel block-table rows (scatters drop), dropped write
    positions, and an out-of-range scatter-back index. Gated like lazy
    growth to paged + greedy + attention-only ("auto" turns it on
    exactly there; "on" elsewhere raises).
    """

    def __init__(self, model, params, *, capacity: int, max_len: int,
                 temperature: float = 0.0, decode_chunk: int = 1,
                 eos_id: Optional[int] = None, seed: int = 0,
                 kv_layout: str = "slots",
                 page_size: int = 16,
                 num_pages: Optional[int] = None,
                 max_pages_per_slot: Optional[int] = None,
                 page_growth: str = "lazy",
                 allocator_wait: Optional[str] = None,
                 prefix_sharing: str = "auto",
                 prefix_cache: str = "off",
                 cache_watermark: Optional[float] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 round_token_budget: Optional[int] = None,
                 fault_plan: Optional[FaultPlan] = None,
                 quarantine_after: int = 3,
                 retry_backoff_s: float = 0.001,
                 allocator_watchdog_s: Optional[float] = None,
                 attention_impl: str = "gather",
                 bucketed_dispatch: str = "auto",
                 sync: Optional[SyncLibrary] = None):
        cfg = model.cfg
        if cfg.is_encdec or cfg.frontend is not None:
            raise ValueError("SlotServeEngine drives decoder-only token LMs")
        if capacity < 1 or decode_chunk < 1:
            raise ValueError("capacity and decode_chunk must be >= 1")
        if kv_layout not in ("slots", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if page_growth not in ("eager", "lazy"):
            raise ValueError(f"unknown page_growth {page_growth!r}")
        if attention_impl not in ("gather", "fused"):
            raise ValueError(f"unknown attention_impl {attention_impl!r}; "
                             f"expected gather or fused")
        if attention_impl == "fused" and kv_layout != "paged":
            raise ValueError("attention_impl='fused' requires "
                             "kv_layout='paged' (the fused kernel walks "
                             "a block table)")
        if bucketed_dispatch not in ("auto", "on", "off"):
            raise ValueError(
                f"unknown bucketed_dispatch {bucketed_dispatch!r}; "
                f"expected auto, on, or off")
        self.model = model
        self.params = params
        self.capacity = capacity
        self.max_len = max_len
        self.temperature = temperature
        self.decode_chunk = decode_chunk
        self.eos_id = eos_id
        self.kv_layout = kv_layout
        self.attention_impl = attention_impl
        # the form the round program's gather reads took, recorded when
        # the decode step is traced (``_chunk_impl``)
        self.kv_grouped = False
        self.sync = sync if sync is not None else SyncLibrary.host_default()
        # read by the benchmark's warm-up to size its queue
        self.plan_window = max(64, 2 * capacity)
        # Right-padded prompt buckets are only sound for attention layers
        # (causal masking hides the pad); Mamba prefill is recurrent, so
        # hybrid/SSM archs prefill at exact prompt length (retrace per
        # distinct length — workloads bucket their own prompts).
        self._can_pad = "mamba" not in cfg.layer_pattern
        # Bucketed compiled dispatch (DESIGN.md §16): sound exactly where
        # the arena is batch-free so only [K]-shaped round state gathers
        # (paged layout — slot-dense contiguous/mamba leaves would gather
        # the whole cache), and where per-row results cannot depend on
        # the dispatch batch shape (argmax is per-row; categorical draws
        # a [B]-shaped key split, so sampling engines stay full-batch).
        bucket_ok = (kv_layout == "paged" and temperature <= 0.0
                     and self._can_pad)
        if bucketed_dispatch == "on" and not bucket_ok:
            raise ValueError(
                "bucketed_dispatch='on' requires kv_layout='paged', "
                "greedy decoding, and attention-only layers")
        self.bucketed_dispatch = (
            bucketed_dispatch == "on"
            or (bucketed_dispatch == "auto" and bucket_ok))
        self._dispatch_cache = (DecodeDispatchCache(capacity)
                                if self.bucketed_dispatch else None)
        # The lazy pause/rollback path only rewinds what the paged k/v
        # scatter touched (length vector; stale writes are re-written
        # before first read). Recurrent state (mamba conv/h) advances
        # destructively on frozen rows, so SSM/hybrid archs stay on
        # eager growth: every page reserved at insert, never paused.
        # Sampling engines stay eager too: a lazy-overflow preemption
        # restarts the victim from its prompt, which only regenerates
        # the identical stream under greedy decoding — with temperature
        # the restart would retract tokens a caller already observed on
        # ServeRequest.out_tokens.
        if kv_layout == "paged" and (not self._can_pad
                                     or temperature > 0.0):
            page_growth = "eager"
        self.page_growth = page_growth if kv_layout == "paged" else "eager"
        # Continuous chunked prefill (DESIGN.md §12): prompts are admitted
        # as bookkeeping only and prefilled C tokens per scheduler round
        # *inside* the decode dispatch, so one long prompt never stalls
        # in-flight decodes for a whole-prompt prefill. Gated like lazy
        # growth: attention-only archs (mamba prefill is recurrent — it
        # cannot resume from a KV cursor) and greedy decoding (a chunked
        # prompt's first token is sampled at completion, a different key
        # order than one-shot; only argmax keeps streams comparable).
        chunk = int(prefill_chunk_tokens) if prefill_chunk_tokens else 0
        if chunk < 0:
            raise ValueError("prefill_chunk_tokens must be >= 1 (or None)")
        if chunk and (not self._can_pad or temperature > 0.0):
            chunk = 0
        self.prefill_chunk = chunk
        # per-round token budget the planner fills: decode rows first,
        # then prefill chunks (scheduler.plan_round). The chunked
        # dispatch computes all K rows at fixed [K, C] shape whether or
        # not they advance, so the default funds every slot — a chunk
        # costs pages, not compute — and a smaller budget is the
        # explicit throttle (it paces page demand, FIFO-fairly).
        self.round_token_budget = (
            int(round_token_budget) if round_token_budget
            else capacity * (decode_chunk + chunk))
        if prefix_sharing not in ("auto", "on", "off"):
            raise ValueError(
                f"unknown prefix_sharing {prefix_sharing!r}; "
                f"expected auto, on, or off")
        if prefix_sharing == "on" and kv_layout != "paged":
            raise ValueError("prefix_sharing requires kv_layout='paged' "
                             "(the contiguous arena has no pages to share)")
        # "auto" turns sharing on exactly where its bit-identity contract
        # holds by construction: paged pages to adopt, greedy decoding
        # (token streams must be comparable on/off), attention prefill
        # (bucketed shapes make donor/adopter K/V shape-identical —
        # mamba prefill runs at exact prompt length and its recurrent
        # state is slot-dense, so there is nothing page-shaped to adopt
        # a prefix from).
        self.prefix_sharing = (
            prefix_sharing == "on"
            or (prefix_sharing == "auto" and kv_layout == "paged"
                and temperature <= 0.0 and self._can_pad))
        # Retained prefix cache (DESIGN.md §14): retirement donates a
        # request's prefix pages to a page-granular trie instead of
        # freeing them; admission adopts the longest cached match via
        # the same incref rider live sharing uses. Gated like sharing:
        # paged pages to hold, greedy decoding (cache on/off streams
        # must stay comparable), attention prefill. Off by default —
        # "auto" turns it on exactly where those conditions hold.
        if prefix_cache not in ("auto", "on", "off"):
            raise ValueError(
                f"unknown prefix_cache {prefix_cache!r}; "
                f"expected auto, on, or off")
        if prefix_cache == "on" and kv_layout != "paged":
            raise ValueError("prefix_cache requires kv_layout='paged' "
                             "(the contiguous arena has no pages to retain)")
        self._cache_enabled = (
            prefix_cache == "on"
            or (prefix_cache == "auto" and kv_layout == "paged"
                and temperature <= 0.0 and self._can_pad))
        # eviction watermark: the free-page floor LRU eviction defends
        # when grants come up short (defaults to the admission headroom)
        self.cache_watermark = (float(cache_watermark)
                                if cache_watermark is not None
                                else _ADMIT_HEADROOM)

        if kv_layout == "paged":
            self.pool = PagedSlotPool(
                model, capacity, max_len, page_size=page_size,
                num_pages=num_pages, max_pages_per_slot=max_pages_per_slot,
                sync=self.sync, wait_mode=allocator_wait,
                expected_contention=allocator_contention(
                    capacity, service_steps=float(max_len)))
        else:
            self.pool = SlotPool(model, capacity, max_len)
        # ---- fault tolerance (DESIGN.md §15): deterministic injection,
        # round-level recovery, and the stuck-holder watchdog. All of it
        # is dormant (zero extra allocator acquires, zero extra state
        # transitions) unless a plan is installed or a round fails.
        self.fault_plan = fault_plan
        self.quarantine_after = max(int(quarantine_after), 1)
        self.retry_backoff_s = float(retry_backoff_s)
        self.rounds_retried = 0
        self.requests_quarantined = 0
        #: rid -> consecutive round failures blamed on it; cleared by
        #: any successful dispatch
        self._round_failures: Dict[int, int] = {}
        if kv_layout == "paged":
            if fault_plan is not None:
                self.pool.pages.fault_hook = fault_plan.alloc_hook
            if allocator_watchdog_s is not None:
                wd = getattr(self.pool.pages.mutex, "set_watchdog", None)
                if wd is not None:
                    wd(allocator_watchdog_s)
        self.admission = AdmissionController(capacity, lib=self.sync)
        self.prefix_index = (PrefixIndex(self.pool.page_size,
                                         self.pool.pages)
                             if self.prefix_sharing else None)
        self.prefix_cache = (PrefixCache(self.pool.page_size,
                                         self.pool.pages)
                             if self._cache_enabled else None)
        if self.prefix_cache is not None:
            # pool.check() audits "every reference has a holder"; the
            # trie's retained references live outside the block tables
            self.pool.register_external_holder(self.prefix_cache.holders)
        # deque: admission pops the FIFO head and preemption pushes the
        # victim back in O(1) — a list's pop(0) shifts the whole backlog
        # on every admission (quadratic over a burst)
        self.queue: Deque[ServeRequest] = collections.deque()
        self.active: Dict[int, ServeRequest] = {}      # slot -> request
        self.finished: List[ServeRequest] = []
        self.grant_log: List[int] = []                 # rids in grant order
        self.step_clock = 0
        self.rounds = 0          # calls of step(): the serve.step number
        self.decode_dispatches = 0
        #: lanes the round programs computed (bucket rows x decode
        #: steps, plus bucket rows x chunk width when a chunk rides) and
        #: those that produced a token or a prompt position
        self.lanes_dispatched = 0
        self.lanes_used = 0
        #: dispatches after which the view's donated arena leaves were
        #: gone: the round program took the arena over and updated it
        #: in place (``stats()["arena_in_place"]``)
        self.arena_in_place = 0
        self.pauses = 0          # slot-rounds a lazy top-up had to wait
        self.preemptions = 0     # lazy-overflow evictions (restart victims)
        self.prefix_hits = 0     # admissions that adopted a live prefix
        self.shared_pages_adopted = 0   # pages incref'd instead of alloc'd
        self.cache_hits = 0      # admissions that adopted a CACHED prefix
        self.cache_tokens_served = 0    # flat positions served from cache
        self.prefill_tokens_saved = 0   # chunked-prefill tokens skipped
        #                                 thanks to cache adoption
        self.cow_splits = 0      # private copies made on divergent writes
        self.prefill_tokens = 0  # real prompt tokens prefilled
        self.pad_tokens = 0      # pad lanes prefill dispatches computed
        self.prefill_chunks = 0  # chunked-prefill row-rounds dispatched
        #: one-shot mode only: rounds where a whole-prompt prefill
        #: dispatch ran while at least one admitted request was decoding
        #: (the decode stall chunking exists to remove — structurally 0
        #: in chunked mode, where admission is bookkeeping and prefill
        #: rides the decode dispatch)
        self.decode_rounds_stalled_by_prefill = 0

        self.cancellations = 0   # requests torn down via cancel()
        self.expiries = 0        # requests shed/evicted past their deadline
        #: rids whose cancellation was requested but not yet applied —
        #: drained at the next round boundary (top of ``step``), where
        #: the slot retires through the existing evict path and its
        #: pages ride the round's one retirement ``free_batch``
        self._cancel_pending: Set[int] = set()
        #: page-id arrays evicted mid-round-boundary (cancellations)
        #: awaiting the round's retirement critical section
        self._deferred_free: List[np.ndarray] = []

        self._next_rid = 0
        self._last_tok = np.zeros(capacity, np.int32)
        self._steps_left = np.zeros(capacity, np.int64)
        # chunked-prefill cursor state machine, per slot: a slot is
        # *prefilling* while _pf_pos < _pf_end (pos = tokens already in
        # cache, end = prompt length); both zero otherwise. Transitions:
        # admitted (pos=adopted prefix, end=lp) → prefilling, +C per
        # granted chunk → decoding (pos=end, both reset to 0) → retired.
        self._pf_pos = np.zeros(capacity, np.int64)
        self._pf_end = np.zeros(capacity, np.int64)
        # the slot's lazy top-up cap: the exact flat positions its
        # request can touch (prompt + max_new - 1 — the last decode
        # writes at position len = prompt+max_new-2 and attends one
        # past it), NOT the eager reserve's +1 slack; chunk-tail writes
        # beyond it drop at the sentinel
        self._grow_cap = np.zeros(capacity, np.int64)
        # generated-boundary registration cursor: full pages of each
        # DECODING slot's prompt+reply already registered in the live
        # index (fork/beam adoption of a still-active conversation)
        self._gen_reg = np.zeros(capacity, np.int64)
        self._key = jax.random.PRNGKey(seed)
        self._prefill = jax.jit(self._prefill_impl,
                                static_argnames=("pad_to",))
        # every round program takes the cache over (donation): the arena
        # it returns is the one it was handed, updated in place, and the
        # view handed in is dead once the call is made (DESIGN.md §18)
        self._chunk = jax.jit(self._chunk_impl, static_argnames=("steps",),
                              donate_argnames=("cache",))
        self._round = jax.jit(self._round_impl,
                              static_argnames=("steps", "chunk"),
                              donate_argnames=("cache",))
        self._bucket_chunk = jax.jit(self._bucket_chunk_impl,
                                     static_argnames=("steps",),
                                     donate_argnames=("cache",))
        self._bucket_round = jax.jit(self._bucket_round_impl,
                                     static_argnames=("steps", "chunk"),
                                     donate_argnames=("cache",))

    # ------------------------------------------------------------ jitted fns
    def _prefill_impl(self, params, tokens, length, *, pad_to):
        # ``pad_to`` is the cache time extent: the full arena row for the
        # contiguous layout (insert slices whole rows), just the prompt
        # bucket for the paged layout (insert scatters pages).
        batch = {"tokens": tokens}
        if length is None:
            logits, cache = self.model.prefill(
                params, batch, max_len=pad_to)
        else:
            logits, cache = self.model.prefill(
                params, batch, max_len=pad_to, length=length)
        return logits, cache

    def _sample(self, logits, key):
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.temperature).astype(jnp.int32)

    def _chunk_impl(self, params, cache, last_tok, frozen, key, *, steps):
        """``steps`` batched decode iterations under one dispatch.

        frozen rows (vacant slots / already-finished requests) keep
        emitting their last token; their cache rows are scratch until the
        slot is reused. Hitting eos freezes a row for the rest of the
        chunk so over-generation past eos never reaches the caller.
        """
        eos = self.eos_id
        cfg = self.model.cfg
        self.kv_grouped = not cfg.attention_free and attn.kv_grouped(
            cfg.num_heads, cfg.num_kv_heads, attn.head_plan(cfg.num_heads)[1])

        def body(carry, key_s):
            cache, tok, frozen = carry
            logits, cache = self.model.decode_step(
                params, cache, tok, attn_impl=self.attention_impl)
            nxt = self._sample(logits, key_s)
            nxt = jnp.where(frozen, tok, nxt)
            if eos is not None:
                frozen = frozen | (nxt == eos)
            return (cache, nxt, frozen), nxt

        keys = jax.random.split(key, steps)
        (cache, tok, frozen), toks = jax.lax.scan(
            body, (cache, last_tok, frozen), keys)
        return cache, tok, toks                        # toks [steps, K]

    def _round_impl(self, params, cache, last_tok, frozen,
                    pf_tok, pf_qpos, pf_wpos, key, *, steps, chunk):
        """One chunked-mode round under ONE dispatch: an optional
        ``chunk``-token prefill sub-step over all K rows (rows not
        advancing carry ``_DROP_POS`` write positions and contribute
        nothing), then the same ``steps``-iteration decode scan as
        ``_chunk_impl``. Static shape is ``(steps, chunk)`` and the
        engine only ever passes ``chunk ∈ {0, C}`` — pure-decode rounds
        take the 0 trace — so scheduler rounds never retrace as the
        prefill/decode mix shifts.

        Order matters: the decode scan runs FIRST. A frozen prefilling
        row still computes its decode steps, scattering scratch K/V at
        ``[cursor, cursor+steps)`` — exactly where this round's chunk
        writes — so the chunk's scatter must land after the scratch to
        overwrite it. The invariant: at every chunk's attention,
        ``[0, cursor+v)`` holds real K/V (earlier chunks wrote
        ``[0, cursor)``, this chunk just wrote ``[cursor, cursor+v)``,
        and scratch beyond is masked by ``kpos <= qpos``); the host then
        rolls the length vector back to the advanced cursor after
        adoption."""
        cache, tok, toks = self._chunk_impl(
            params, cache, last_tok, frozen, key, steps=steps)
        pf_logits = None
        if chunk:
            pf_logits, cache = self.model.prefill_chunk(
                params, cache, pf_tok, pf_qpos, pf_wpos)
        return cache, tok, toks, pf_logits

    # ---- bucketed dispatch (DESIGN.md §16): gather the active slots
    # into a [kb]-row view, run the ordinary round body at that fixed
    # shape, scatter back to [K]. Pad lanes (row id == capacity) are
    # inert end to end: zero length, sentinel block-table row (arena
    # scatters drop), frozen (token stream pinned), _DROP_POS write
    # positions, and an out-of-range scatter-back index (mode="drop").
    # The arena leaves are batch-free under the paged layout, so only
    # the [K]-shaped round state gathers — everything downstream of the
    # dispatch (adopt, harvest, rollback) is unchanged.
    def _bucket_gather(self, cache, rows, last_tok, frozen):
        K = self.capacity
        pad = rows >= K
        r = jnp.minimum(rows, K - 1)
        sentinel = jnp.int32(self.pool.pages.num_pages)
        cache_b = dict(cache)
        cache_b["len"] = jnp.where(pad, 0, cache["len"][r])
        cache_b["pages"] = jnp.where(
            pad[:, None], sentinel, cache["pages"][r])
        return pad, r, cache_b, jnp.where(pad, 0, last_tok[r]), \
            frozen[r] | pad

    def _bucket_scatter(self, cache, rows, pad, cache_b, tok_b, toks_b,
                        last_tok, steps):
        K = self.capacity
        drop = jnp.where(pad, K, rows)      # out-of-range writes drop
        out = dict(cache_b)
        out["len"] = cache["len"].at[drop].set(cache_b["len"], mode="drop")
        out["pages"] = cache["pages"]       # host-owned, pass-through
        tok = last_tok.at[drop].set(tok_b, mode="drop")
        toks = jnp.broadcast_to(last_tok[None, :], (steps, K))
        toks = toks.at[:, drop].set(toks_b, mode="drop")
        return out, tok, toks

    def _bucket_chunk_impl(self, params, cache, rows, last_tok, frozen,
                           key, *, steps):
        # trace-time side effect: fires once per new (kb, steps) shape,
        # never on a cached dispatch — the ledger the retrace-count
        # property test audits
        self._dispatch_cache.record_trace(("decode", rows.shape[0], steps))
        pad, _, cache_b, lt, fr = self._bucket_gather(
            cache, rows, last_tok, frozen)
        cache_o, tok_b, toks_b = self._chunk_impl(
            params, cache_b, lt, fr, key, steps=steps)
        return self._bucket_scatter(
            cache, rows, pad, cache_o, tok_b, toks_b, last_tok, steps)

    def _bucket_round_impl(self, params, cache, rows, last_tok, frozen,
                           pf_tok, pf_qpos, pf_wpos, key, *,
                           steps, chunk):
        self._dispatch_cache.record_trace(
            ("round", rows.shape[0], steps, chunk))
        pad, r, cache_b, lt, fr = self._bucket_gather(
            cache, rows, last_tok, frozen)
        pfw = jnp.where(pad[:, None], jnp.int32(_DROP_POS), pf_wpos[r])
        cache_o, tok_b, toks_b, pf_logits_b = self._round_impl(
            params, cache_b, lt, fr, pf_tok[r], pf_qpos[r], pfw, key,
            steps=steps, chunk=chunk)
        out, tok, toks = self._bucket_scatter(
            cache, rows, pad, cache_o, tok_b, toks_b, last_tok, steps)
        pf_logits = None
        if chunk:
            drop = jnp.where(pad, self.capacity, rows)
            pf_logits = jnp.zeros(
                (self.capacity, chunk, pf_logits_b.shape[-1]),
                pf_logits_b.dtype).at[drop].set(pf_logits_b, mode="drop")
        return out, tok, toks, pf_logits

    # ------------------------------------------------------------ submission
    def submit(self, prompt, max_new_tokens: int,
               rid: Optional[int] = None,
               deadline_step: Optional[int] = None,
               deadline_s: Optional[float] = None) -> ServeRequest:
        """Queue a request. ``deadline_step`` / ``deadline_s`` are
        *absolute* deadlines (step clock / ``time.perf_counter()``):
        past either, the request is shed from the queue as EXPIRED, and
        once active it turns *late* — deprioritized for chunk grants
        and the preferred page-pressure eviction victim (DESIGN.md
        §13). No deadline means the pre-SLO behavior, unchanged."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size + max_new_tokens + 1 > self.pool.virtual_max_len:
            raise ValueError(
                f"prompt({prompt.size}) + max_new({max_new_tokens}) "
                f"exceeds slot max_len({self.pool.virtual_max_len})")
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid) + 1
        req = ServeRequest(rid=rid, prompt=prompt,
                           max_new_tokens=max_new_tokens,
                           arrival_step=self.step_clock,
                           arrival_s=time.perf_counter(),
                           deadline_step=deadline_step,
                           deadline_s=deadline_s)
        self.queue.append(req)
        return req

    # ---------------------------------------------------------- cancellation
    def cancel(self, rid: int) -> bool:
        """Request cancellation of ``rid``. Returns True when the
        request was still live (queued or active) at the call.

        A queued request is torn down immediately — it holds no slot
        and no pages. An active request is marked and retired at the
        *next round boundary* (top of the next ``step``): its slot and
        semaphore grant free before that round's admission runs, and
        its pages ride the round's existing retirement ``free_batch``
        critical section — cancellation adds zero allocator acquires.
        Shared (prefix-adopted) pages need no special casing: the free
        is a decref, so a page a surviving adopter still reads outlives
        the cancelled holder (DESIGN.md §13).
        """
        for req in self.queue:
            if req.rid == rid:
                self.queue.remove(req)
                req.state = RequestState.CANCELLED
                req.finish_step = self.step_clock
                req.finish_s = time.perf_counter()
                self.finished.append(req)
                self.cancellations += 1
                return True
        for req in self.active.values():
            if req.rid == rid:
                self._cancel_pending.add(rid)
                return True
        return rid in self._cancel_pending

    def _apply_cancels(self) -> int:
        """Round-boundary cancellation: retire every marked active slot
        through the existing evict path, deferring the page frees into
        the round's retirement batch (``_retire_batch`` drains them in
        the same critical section as natural retirements)."""
        if not self._cancel_pending:
            return 0
        rids, self._cancel_pending = self._cancel_pending, set()
        slots = [s for s, r in self.active.items() if r.rid in rids]
        for slot in slots:
            req = self.active.pop(slot)
            req.state = RequestState.CANCELLED
            req.finish_step = self.step_clock
            req.finish_s = time.perf_counter()
            # capture the written extent BEFORE the cursors reset: a
            # request cancelled mid-(chunked)-prefill still donates its
            # prefilled full pages to the prefix cache (§13/§14)
            pf_pos = (int(self._pf_pos[slot])
                      if self._prefilling(slot) else None)
            self._steps_left[slot] = 0
            self._grow_cap[slot] = 0
            self._pf_pos[slot] = 0
            self._pf_end[slot] = 0
            if self.kv_layout == "paged":
                held = self.pool.evict(slot, free_pages=False)
                held = self._donate_on_retire(req, held, prefill_pos=pf_pos)
                if held is not None and held.size:
                    self._deferred_free.append(held)
            else:
                self.pool.evict(slot)
            self.admission.release_slot()
            self.finished.append(req)
            self.cancellations += 1
        return len(slots)

    # -------------------------------------------------------------- deadlines
    def _expire_queued(self) -> int:
        """Shed queued requests whose deadline already passed — they
        could not produce a first token in time, so granting them a
        slot would only burn pages. Runs before admission so an expired
        request is never granted a slot."""
        if not any(r.deadline_step is not None or r.deadline_s is not None
                   for r in self.queue):
            return 0
        now_s = time.perf_counter()
        keep: Deque[ServeRequest] = collections.deque()
        n = 0
        for req in self.queue:
            if req.past_deadline(self.step_clock, now_s):
                req.state = RequestState.EXPIRED
                req.finish_step = self.step_clock
                req.finish_s = now_s
                self.finished.append(req)
                self.expiries += 1
                n += 1
            else:
                keep.append(req)
        self.queue = keep
        return n

    def _late(self, slot: int) -> bool:
        """Whether the active request in ``slot`` is past its deadline
        (late rows are deprioritized for chunk grants and evicted first
        under page pressure)."""
        return self.active[slot].past_deadline(self.step_clock)

    def _flush_deferred_frees(self) -> None:
        """Return cancellation-deferred pages when the round ends
        without reaching ``_retire_batch`` (early exits of ``step``)."""
        if self._deferred_free:
            self._free_batch_safe(self._deferred_free)
            self._deferred_free = []

    # -------------------------------------------------- fault recovery (§15)
    def _faults_off(self):
        """Context manager suppressing fault injection — recovery and
        compensation paths run under this so a rollback can never
        itself be faulted into a wedge."""
        if self.fault_plan is not None:
            return self.fault_plan.suspended()
        return contextlib.nullcontext()

    def _free_batch_safe(self, groups) -> List[int]:
        """``pages.free_batch`` that survives an injected mid-batch
        fault: the pool's undo log already rolled the batch back, so
        the retry (injection suspended) applies it cleanly. Real
        allocator errors (``PageLeakError``) still propagate — only
        deliberate faults are absorbed."""
        if not groups:
            return []
        try:
            return self.pool.pages.free_batch(groups)
        except InjectedFault:
            with self._faults_off():
                return self.pool.pages.free_batch(groups)

    def _quarantine(self, rid: int, exc: BaseException) -> None:
        """Evict exactly one repeatedly-blamed request into the FAILED
        terminal state. Its pages ride the normal deferred-free path
        (the next retirement batch, or the round-end flush) and its
        slot + semaphore grant free immediately, so survivors keep
        decoding untouched. Nothing is donated to the prefix cache —
        a failed request's K/V is suspect by definition."""
        slot = next(s for s, r in self.active.items() if r.rid == rid)
        req = self.active.pop(slot)
        req.state = RequestState.FAILED
        req.error = str(exc)
        req.finish_step = self.step_clock
        req.finish_s = time.perf_counter()
        self._steps_left[slot] = 0
        self._grow_cap[slot] = 0
        self._pf_pos[slot] = 0
        self._pf_end[slot] = 0
        self._gen_reg[slot] = 0
        if self.kv_layout == "paged":
            held = self.pool.evict(slot, free_pages=False)
            if held is not None and held.size:
                self._deferred_free.append(held)
        else:
            self.pool.evict(slot)
        self.admission.release_slot()
        self.finished.append(req)
        self.requests_quarantined += 1

    def _recover_round(self, exc: BaseException) -> None:
        """Blame-attribute one round failure and quarantine the culprit
        once it crosses ``quarantine_after`` consecutive failures. The
        fault's own rid wins when it names a live request; otherwise
        blame falls on the newest grant — the request whose admission
        most recently changed the round's shape."""
        live = {r.rid for r in self.active.values()}
        if not live:
            return
        rid = getattr(exc, "rid", None)
        if rid is None or rid not in live:
            rid = max(live)
        self._round_failures[rid] = self._round_failures.get(rid, 0) + 1
        if self._round_failures[rid] >= self.quarantine_after:
            self._quarantine(rid, exc)
            self._round_failures.pop(rid, None)

    # ------------------------------------------------------------- admission
    def _bucket_len(self, n: int) -> int:
        if not self._can_pad:
            return n
        b = 8
        while b < n:
            b *= 2
        # never pad past what a slot can hold — the prompt itself fits by
        # the submit() check, and _pad_cache cannot pad to less than s
        return min(b, self.pool.virtual_max_len)

    def _headroom_pages(self) -> int:
        """Admission watermark in pages: keep this many pages free for
        in-flight top-ups when admitting under lazy growth."""
        return int(np.ceil(_ADMIT_HEADROOM * self.pool.pages.num_pages))

    def _watermark_pages(self) -> int:
        """Free-page floor the prefix cache's LRU eviction defends: when
        a round's grants would leave fewer free pages, LRU leaves are
        trimmed (their decrefs riding that round's existing critical
        section) until the floor holds or the cache is empty."""
        return int(np.ceil(self.cache_watermark * self.pool.pages.num_pages))

    def _lookup_prefix(self, prompt, bucket: int, schedule: int
                       ) -> Tuple[int, Optional[np.ndarray], bool]:
        """Longest prefix match across BOTH indexes — the live
        :class:`PrefixIndex` (pages some active slot still holds) and
        the retained :class:`PrefixCache` (pages donated by retirees).
        Returns ``(matched_tokens, page_ids, from_cache)``; the longest
        match wins, ties to the CACHE. The tie-break matters: a live
        entry for a retired request's pages stays valid precisely
        because the cache retains them, so on a tie both name the same
        physical pages — crediting the cache touches its LRU clock,
        and a retention policy that never saw these reuse hits would
        evict exactly the conversations being re-served. A strictly
        longer live match (e.g. a partial-tail entry past the cache's
        page granularity) still wins."""
        sh_len, sh_ids = 0, None
        if self.prefix_sharing:
            sh_len, sh_ids = self.prefix_index.lookup(prompt, bucket,
                                                      schedule=schedule)
        from_cache = False
        if self.prefix_cache is not None:
            c_len, c_ids = self.prefix_cache.lookup(
                prompt, cache_key_suffix(bucket, schedule))
            if c_ids is not None and c_len >= sh_len:
                sh_len, sh_ids, from_cache = c_len, c_ids, True
        return sh_len, sh_ids, from_cache

    def _plan_evictions(self, deficit: int) -> Tuple[List[np.ndarray], int]:
        """Ask the cache's LRU for ``deficit`` reclaimable pages.
        Returns ``(groups, freeable)`` — the caller MUST hand every
        group to its next allocator critical section as decrefs (the
        trie has already forgotten them); a caller that ends up not
        entering one stashes them in ``_deferred_free`` instead."""
        if (self.prefix_cache is None or deficit <= 0
                or self.prefix_cache.pages_held <= 0):
            return [], 0
        return self.prefix_cache.evict_plan(deficit)

    def _evict_credit(self, evict_groups: List[np.ndarray],
                      adopt_groups) -> int:
        """Pages the planned evictions will actually return to the free
        list: refcount-1 pages NOT re-adopted by the same batch. A
        live-index (or pre-plan cache) match can name a page the plan
        also drops — its adoption incref keeps the page allocated, so
        counting it as free would over-admit and trip the all-or-nothing
        reserve. Recomputed at every gate: staging one more request can
        invalidate the credit of an earlier plan."""
        if not evict_groups:
            return 0
        adopt = {int(p) for g in adopt_groups if g is not None
                 for p in np.asarray(g).reshape(-1)}
        credit = 0
        for g in evict_groups:
            rc = self.pool.pages.refcounts(g)
            credit += sum(1 for p, r in zip(g.tolist(), rc.tolist())
                          if r == 1 and int(p) not in adopt)
        return credit

    def _abort_admission(self, staged_pairs, evict_groups) -> None:
        """An injected allocator fault aborted the admission batch (the
        pool's undo log already rolled every grant/incref/decref back).
        Un-stage: slots and semaphore grants return, the staged
        requests go back to the queue front in arrival order (FIFO
        intact — they re-admit next round), and the planned cache
        evictions are re-applied under suspended injection: the trie
        already forgot those pages, so dropping their decrefs would
        leak them."""
        for req, slot in reversed(staged_pairs):
            self.pool.evict(slot, free_pages=False)
            self.admission.release_slot()
            self.queue.appendleft(req)
        with self._faults_off():
            self._free_batch_safe(evict_groups)
        self.rounds_retried += 1

    def _admit(self) -> int:
        """Admit the FIFO front of the queue that the free slots hold.

        Page grants for the whole admission batch go through ONE
        allocator critical section (``reserve_batch``): staging first
        decides and acquires slots, then the batch allocs, then each
        request prefills into its pre-granted pages. Under lazy growth
        the initial grant is just the prefill bucket — the worst case is
        only page-*bounded*, not reserved — and the gate is the headroom
        watermark instead of ``can_reserve(worst_case)``.

        With prefix sharing on, staging also looks each prompt up in
        the prefix index: adopted pages are incref'd *inside the same
        reserve_batch critical section* and only the private remainder
        is granted, so sharing changes what the one acquire does, not
        how many there are. Admission order is untouched: the lookup
        happens only for the FIFO head the free slots already admit — a
        prefix hit never lets a younger request jump a page-starved
        older one. Requests admitted in the same batch cannot adopt
        from each other (the donor's pages exist only after its
        insert); the index warms for the next round.
        """
        had_decoders = bool(self.active)
        n_admit = min(self.pool.n_free, len(self.queue))
        if self.prefill_chunk:
            with TraceAnnotation("serve.grant"):
                return self._admit_chunked(n_admit)
        # staging (prefix lookups, slot grants) and the one allocator
        # critical section; the prefills after it are model dispatches
        with TraceAnnotation("serve.grant"):
            staged = []    # (req, slot, lp, bucket, reserve, grant, sh_ids,
            #                 sh_len, from_cache)
            staged_pages = 0
            evict_groups: List[np.ndarray] = []   # cache LRU leaves to drop
            evict_credit = 0                      # pages those drops free
            lazy = self.kv_layout == "paged" and self.page_growth == "lazy"
            while len(staged) < n_admit and self.queue and self.pool.n_free:
                req = self.queue[0]
                lp = int(req.prompt.size)
                bucket = self._bucket_len(lp)
                # worst-case flat positions (prompt bucket ∪ prompt+new+1):
                # reserved now under eager growth (decode never allocates
                # mid-dispatch), merely bounded under lazy growth. Either
                # way a page-starved FIFO head waits for retirements to
                # reclaim pages — later requests do not jump it.
                reserve = max(bucket, lp + req.max_new_tokens + 1)
                # lazy initial grant: the prefill bucket plus the first
                # lookahead window, never past what the request can actually
                # touch — short requests only ever hold pages they can fill
                need = max(lp + req.max_new_tokens - 1, lp)
                grant = (max(bucket,
                             min(bucket + self.decode_chunk
                                 * _PAGE_LOOKAHEAD_CHUNKS, need))
                         if lazy else reserve)
                sh_len, sh_ids, from_cache = self._lookup_prefix(
                    req.prompt, bucket, 0)
                n_shared = 0 if sh_ids is None else int(sh_ids.size)
                if self.kv_layout == "paged":
                    def fits(extra: int) -> bool:
                        return (self.pool.can_admit_lazy(
                                    grant, reserve,
                                    headroom_pages=self._headroom_pages(),
                                    pending_pages=staged_pages,
                                    shared_pages=n_shared, extra_free=extra)
                                if lazy else
                                self.pool.can_reserve(
                                    reserve, pending_pages=staged_pages,
                                    shared_pages=n_shared, extra_free=extra))

                    def credit() -> int:
                        return self._evict_credit(
                            evict_groups,
                            [t[6] for t in staged] + [sh_ids])
                    evict_credit = credit()
                    if not fits(evict_credit):
                        # short on pages: ask the cache's LRU to cover the
                        # worst-case deficit — the drops ride this batch's
                        # reserve_batch (no extra acquire)
                        deficit = (
                            max(self.pool.pages.pages_for(grant) - n_shared, 0)
                            + staged_pages + self._headroom_pages()
                            - self.pool.pages.n_free - evict_credit)
                        groups, _ = self._plan_evictions(deficit)
                        evict_groups.extend(groups)
                        if not fits(credit()):
                            break
                self.queue.popleft()
                # Algorithm-5 wait(): never blocks here because the round
                # admits no more requests than there are free slots.
                if not self.admission.acquire_slot(timeout=5.0):
                    self.queue.appendleft(req)
                    break
                slot = self.pool.acquire(req.rid)
                staged.append((req, slot, lp, bucket, reserve, grant,
                               sh_ids, sh_len, from_cache))
                if self.kv_layout == "paged":
                    staged_pages += max(
                        self.pool.pages.pages_for(grant) - n_shared, 0)
            if not staged:
                # planned evictions must still land (the trie already
                # forgot them): they ride the round's retirement batch
                self._deferred_free.extend(evict_groups)
                return 0

            # one allocator critical section for the whole admission batch
            # (private grants, shared-prefix increfs, AND cache-eviction
            # decrefs together)
            if self.kv_layout == "paged":
                try:
                    grants = self.pool.reserve_batch(
                        [(slot, grant)
                         for (_, slot, _, _, _, grant, _, _, _) in staged],
                        shared=[sh_ids for (*_, sh_ids, _, _) in staged],
                        evict=evict_groups or None)
                except InjectedFault:
                    self._abort_admission([(t[0], t[1]) for t in staged],
                                          evict_groups)
                    return 0
            else:
                grants = [None] * len(staged)

        instant = []               # eos/0-budget on the prefill token
        for (req, slot, lp, bucket, reserve, grant,
             sh_ids, sh_len, from_cache), ids in zip(staged, grants):
            padded = np.zeros(bucket, np.int32)
            padded[:lp] = req.prompt
            length = (jnp.asarray([lp], jnp.int32)
                      if bucket != lp else None)
            logits, cache = self._prefill(
                self.params, jnp.asarray(padded)[None, :], length,
                pad_to=bucket if self.kv_layout == "paged" else self.max_len)
            self.prefill_tokens += lp
            self.pad_tokens += bucket - lp
            self._key, sub = jax.random.split(self._key)
            tok0 = int(self._sample(logits, sub)[0])
            if self.kv_layout == "paged":
                self.pool.insert(slot, cache, lp, reserve=grant, ids=ids,
                                 shared_ids=sh_ids, shared_len=sh_len)
                if sh_ids is not None and sh_ids.size:
                    self.prefix_hits += 1
                    self.shared_pages_adopted += int(sh_ids.size)
                    if from_cache:
                        self.cache_hits += 1
                        self.cache_tokens_served += sh_len
                if self.prefix_sharing:
                    self.prefix_index.register(
                        req.prompt, bucket,
                        self.pool.page_ids(
                            slot, self.pool.pages.pages_for(lp)))
            else:
                self.pool.insert(slot, cache, lp, reserve=reserve)
            self._last_tok[slot] = tok0
            self._steps_left[slot] = req.max_new_tokens - 1
            self._grow_cap[slot] = max(lp + req.max_new_tokens - 1, lp)
            if self.kv_layout == "paged":
                self._gen_reg[slot] = lp // self.pool.page_size
            req.slot = slot
            if req.preemptions == 0 or req.grant_step < 0:
                # a preempted request was already granted once: its FIFO
                # log entry and wait-time stats belong to that grant
                req.grant_step = self.step_clock
                req.grant_s = time.perf_counter()
                self.grant_log.append(req.rid)
            # one-shot mode prefills inside the granting round, so the
            # PREFILLING state is instantaneous on the step clock
            req.state = RequestState.DECODING
            req.decode_start_step = self.step_clock
            req.out_tokens.append(tok0)
            if self.eos_id is not None and tok0 == self.eos_id:
                req.eos = True
            self.active[slot] = req
            if req.eos or self._steps_left[slot] <= 0:
                instant.append((slot, 0))
        self._retire_batch(instant)
        if had_decoders:
            # this round's decode dispatch waited for len(staged)
            # whole-prompt prefill dispatches — the stall chunked
            # prefill removes
            self.decode_rounds_stalled_by_prefill += 1
        return len(staged)

    def _admit_chunked(self, n_admit: int) -> int:
        """Chunked-mode admission: pure bookkeeping, NO model dispatch.

        A granted request gets a slot, pages for its first chunk(s), and
        a prefill cursor — the chunks themselves ride later rounds'
        decode dispatches. Because nothing is prefilled here, admission
        happens rounds earlier under page pressure than the one-shot
        path (which must afford the whole prompt bucket up front); that
        earlier grant_step is the p99 queue-wait win.

        Page sizing under lazy growth is two-tier: try a *generous*
        grant first (the whole prompt plus the decode lookahead — lock
        parity with one-shot when pages are abundant), fall back to
        just the first chunk when the watermark would block it (the
        early-admission win when pages are scarce; later chunks ride
        the per-round top-up's existing acquire).

        Prefix adoption keys the index by ``schedule=C`` (one-shot
        entries use 0) — chunk boundaries are canonical multiples of C,
        so same-C donors are bit-identical by construction and
        schedules never cross-adopt. The adopted prefix is trimmed to a
        multiple of lcm(page_size, C): adoption means *skipping whole
        chunks*, keeping every resumed chunk canonically aligned. The
        last chunk always stays private — the completion logits must
        come from a chunk this engine runs.
        """
        staged = []       # (req, slot, lp, grant, sh_ids, sh_len, from_cache)
        staged_pages = 0
        evict_groups: List[np.ndarray] = []
        evict_credit = 0
        C = self.prefill_chunk
        lazy = self.kv_layout == "paged" and self.page_growth == "lazy"
        while len(staged) < n_admit and self.queue and self.pool.n_free:
            req = self.queue[0]
            lp = int(req.prompt.size)
            need = max(lp + req.max_new_tokens - 1, lp)
            reserve = lp + req.max_new_tokens + 1
            sh_len, sh_ids, from_cache = self._lookup_prefix(
                req.prompt, 0, C)
            if sh_ids is not None:
                ps = self.pool.page_size
                align = ps * C // math.gcd(ps, C)
                keep = (min(sh_len, lp - 1) // align) * align
                n_keep = keep // ps
                if n_keep <= 0:
                    sh_len, sh_ids, from_cache = 0, None, False
                else:
                    sh_ids, sh_len = sh_ids[:n_keep], keep
            n_shared = 0 if sh_ids is None else int(sh_ids.size)
            if self.kv_layout == "paged":
                first = min(sh_len + C, need)
                window = min(sh_len + C * _PAGE_LOOKAHEAD_CHUNKS, need)
                generous = min(max(lp, first)
                               + self.decode_chunk
                               * _PAGE_LOOKAHEAD_CHUNKS, need)

                def pick(extra: int) -> Optional[int]:
                    if lazy:
                        # tiered grant: whole prompt + decode lookahead
                        # when pages allow (lock parity with one-shot:
                        # later chunks find their pages pre-granted),
                        # else a chunk-lookahead window, else just the
                        # first chunk — the early-admission win when
                        # pages are scarce
                        for g in (generous, window, first):
                            if self.pool.can_admit_lazy(
                                    g, reserve,
                                    headroom_pages=self._headroom_pages(),
                                    pending_pages=staged_pages,
                                    shared_pages=n_shared,
                                    extra_free=extra):
                                return g
                    elif self.pool.can_reserve(reserve,
                                               pending_pages=staged_pages,
                                               shared_pages=n_shared,
                                               extra_free=extra):
                        return reserve
                    return None

                def credit() -> int:
                    return self._evict_credit(
                        evict_groups,
                        [t[4] for t in staged] + [sh_ids])
                evict_credit = credit()
                grant = pick(evict_credit)
                if grant is None:
                    # cover the smallest viable tier from the cache's
                    # LRU — the drops ride this batch's reserve_batch
                    deficit = (
                        max(self.pool.pages.pages_for(
                            first if lazy else reserve) - n_shared, 0)
                        + staged_pages + self._headroom_pages()
                        - self.pool.pages.n_free - evict_credit)
                    groups, _ = self._plan_evictions(deficit)
                    evict_groups.extend(groups)
                    grant = pick(credit())
                if grant is None:
                    break
            else:
                grant = 0
            self.queue.popleft()
            if not self.admission.acquire_slot(timeout=5.0):
                self.queue.appendleft(req)
                break
            slot = self.pool.acquire(req.rid)
            staged.append((req, slot, lp, grant, sh_ids, sh_len,
                           from_cache))
            if self.kv_layout == "paged":
                staged_pages += max(
                    self.pool.pages.pages_for(grant) - n_shared, 0)
        if not staged:
            self._deferred_free.extend(evict_groups)
            return 0

        # the one allocator critical section admission costs — same as
        # one-shot (private grants, shared-prefix increfs, and cache-
        # eviction decrefs together)
        if self.kv_layout == "paged":
            try:
                grants = self.pool.reserve_batch(
                    [(slot, grant)
                     for (_, slot, _, grant, _, _, _) in staged],
                    shared=[sh_ids for (*_, sh_ids, _, _) in staged],
                    evict=evict_groups or None)
            except InjectedFault:
                self._abort_admission([(t[0], t[1]) for t in staged],
                                      evict_groups)
                return 0
        else:
            grants = [None] * len(staged)

        for (req, slot, lp, grant, sh_ids, sh_len,
             from_cache), ids in zip(staged, grants):
            if self.kv_layout == "paged":
                self.pool.assign(slot, ids=ids, shared_ids=sh_ids,
                                 length=sh_len)
                if sh_ids is not None and sh_ids.size:
                    self.prefix_hits += 1
                    self.shared_pages_adopted += int(sh_ids.size)
                    # adopted chunks are SKIPPED chunks no matter which
                    # index served the match: the cursor starts at
                    # sh_len, so these prompt tokens are never
                    # dispatched — real compute saved. (A live entry
                    # for retired pages only stayed valid because the
                    # cache retained them, so the saving is cache-
                    # enabled even when attribution goes to the index.)
                    self.prefill_tokens_saved += sh_len
                    if from_cache:
                        self.cache_hits += 1
                        self.cache_tokens_served += sh_len
            else:
                self.pool.assign(slot, length=sh_len)
            self._pf_pos[slot] = sh_len        # adoption = skipped chunks
            self._pf_end[slot] = lp
            self._last_tok[slot] = 0
            self._steps_left[slot] = req.max_new_tokens - 1
            self._grow_cap[slot] = max(lp + req.max_new_tokens - 1, lp)
            if self.kv_layout == "paged":
                self._gen_reg[slot] = lp // self.pool.page_size
            req.slot = slot
            if req.preemptions == 0 or req.grant_step < 0:
                req.grant_step = self.step_clock
                req.grant_s = time.perf_counter()
                self.grant_log.append(req.rid)
            req.state = RequestState.PREFILLING
            self.active[slot] = req
        return len(staged)

    def _donate_on_retire(self, req: "ServeRequest", held: np.ndarray,
                          prefill_pos: Optional[int] = None
                          ) -> Optional[np.ndarray]:
        """Offer a retiring request's written prefix to the prefix
        cache; returns the pages still to be freed (``held`` minus
        whatever the trie kept — the cache *inherits* the retiree's
        reference for kept pages, so excluding them from the free group
        IS the donation: zero extra pool calls, zero extra acquires).

        The donated extent is exactly the positions holding real K/V:
        the prompt plus every *written* reply token — the final sampled
        token is never written, and a chunk's post-eos scan lanes write
        only past the extent (outside any donated full page). A request
        cancelled mid-(chunked)-prefill donates up to its cursor
        (``prefill_pos``): the §13 "a cancelled donor still donates"
        rule.
        """
        if self.prefix_cache is None or held is None or not held.size:
            return held
        lp = int(req.prompt.size)
        if prefill_pos is not None:
            extent = int(prefill_pos)
            tokens = req.prompt[:extent]
            gen_from = None
        else:
            out = req.out_tokens
            tokens = np.concatenate(
                [req.prompt,
                 np.asarray(out[:-1], np.int32)]) if out else req.prompt
            extent = int(tokens.size)
            gen_from = lp if extent > lp else None
        if extent < self.pool.page_size:
            return held
        # donor pages live under the donor's dispatch-shape root: the
        # §11/§12 shape-identity rule, carried into retention
        suffix = (cache_key_suffix(0, self.prefill_chunk)
                  if self.prefill_chunk
                  else cache_key_suffix(self._bucket_len(lp), 0))
        kept, _dup = self.prefix_cache.donate(
            tokens, held, suffix, generated_from=gen_from)
        if kept.size:
            held = held[~np.isin(held, kept)]
        return held

    def _retire_batch(self, pairs: List[Tuple[int, int]]) -> None:
        """Retire ``(slot, step_offset)`` pairs; under the paged layout
        every retirement's pages return in ONE allocator critical
        section (deferred-free eviction). Pages deferred by this
        round's cancellations ride the same critical section — a round
        with cancellations pays exactly the retirement acquire it
        would have paid anyway. With the prefix cache on, each
        retiree's full prefix pages are *donated* first (refcount
        inheritance — the kept pages simply stay out of the free
        group) and only the remainder is freed."""
        deferred = []
        for slot, offset in pairs:
            req = self.active.pop(slot)
            req.state = RequestState.FINISHED
            req.finish_step = self.step_clock + offset
            req.finish_s = time.perf_counter()
            self._steps_left[slot] = 0
            if self.kv_layout == "paged":
                held = self.pool.evict(slot, free_pages=False)
                held = self._donate_on_retire(req, held)
                if held is not None and held.size:
                    deferred.append(held)
            else:
                self.pool.evict(slot)
            self.admission.release_slot()
            self.finished.append(req)
        if self._deferred_free:
            deferred = self._deferred_free + deferred
            self._deferred_free = []
        if deferred:
            self._free_batch_safe(deferred)

    def _retire(self, slot: int, offset: int) -> None:
        self._retire_batch([(slot, offset)])

    # --------------------------------------------------- lazy page growth
    def _preempt(self, slot: int) -> None:
        """Lazy-overflow eviction: kick the victim out, reclaiming its
        pages so older slots can grow. An on-time victim goes back to
        the queue front and restarts from its prompt on re-admission
        (greedy decoding regenerates the identical stream; its original
        grant keeps the FIFO log entry and wait stats). A victim past
        its deadline *expires* instead — regenerating a stream that can
        no longer meet its SLO would burn pages the on-time rows need,
        which is exactly why late rows are picked as victims first."""
        req = self.active.pop(slot)
        late = req.past_deadline(self.step_clock)
        if self.kv_layout == "paged":
            # immediate free (rare path), but through the fault-safe
            # helper: the preemption exists to reclaim pages NOW for a
            # starving slot, so an injected fault in the free must not
            # strand them
            held = self.pool.evict(slot, free_pages=False)
            if held is not None and held.size:
                self._free_batch_safe([held])
        else:
            self.pool.evict(slot)
        self.admission.release_slot()
        self._steps_left[slot] = 0
        self._grow_cap[slot] = 0
        self._pf_pos[slot] = 0                 # chunked: restart the prompt
        self._pf_end[slot] = 0                 # cursor from scratch too
        self._gen_reg[slot] = 0
        req.slot = -1
        if late:
            req.state = RequestState.EXPIRED
            req.finish_step = self.step_clock
            req.finish_s = time.perf_counter()
            self.finished.append(req)
            self.expiries += 1
            return
        req.state = RequestState.QUEUED
        req.eos = False
        req.out_tokens = []
        req.preemptions += 1
        self.preemptions += 1
        self.queue.appendleft(req)             # FIFO: it predates the queue

    def _split_plan(self, order: List[int], lens: np.ndarray,
                    steps: int) -> List[Tuple[int, int]]:
        """CoW split plan for this round: every ``(slot, table_idx)``
        whose coming write (flat positions ``[len, len+steps)``)
        targets a shared (refcount > 1) page — except one *keeper* per
        page: when every holder of a
        page is about to write it, the holder with the longest context
        keeps it in place (its writes start past every other holder's
        readable prefix, so nothing anyone still reads is touched) and
        only the rest pay for copies. The keeper's write is sound
        because the others' decrefs land in the same critical section
        as the copies' grants, before the dispatch."""
        targets: Dict[int, List[Tuple[int, int]]] = {}   # page -> [(slot, j)]
        for s in order:
            hits = self.pool.shared_write_targets(
                s, int(lens[s]), int(lens[s]) + steps)
            for j, page in hits:
                targets.setdefault(page, []).append((s, j))
        plan: List[Tuple[int, int]] = []
        for page, writers in targets.items():
            rc = int(self.pool.pages.refcounts([page])[0])
            if rc == len(writers):
                # all holders are writers: the longest context keeps the
                # page (max len; ties to the oldest grant) — everyone
                # else splits, so post-split refcount is exactly 1
                keeper = max(
                    writers,
                    key=lambda sj: (int(lens[sj[0]]),
                                    -self.active[sj[0]].rid))
                writers = [w for w in writers if w != keeper]
            plan.extend(writers)
        return plan

    def _prefilling(self, slot: int) -> bool:
        return self._pf_pos[slot] < self._pf_end[slot]

    def _grow_for_chunk(self, steps: int,
                        chunk_rows: Tuple[int, ...] = ()) -> Tuple[set, set]:
        """The per-round page-prep pass: ONE allocator critical section
        covers the lazy top-ups (every decoding slot up to the pages
        this chunk's writes and reads need, capped at the
        admission-time worst case; every *planned prefill chunk* up to
        its coming chunk window, capped at the prompt length), and the
        CoW splits (a private copy for every shared page some decoding
        slot is about to write — ``PagedSlotPool.prepare_batch``).
        Chunked-prefill page demand adds NO critical section: its items
        fold into the same batch.

        Prefilling rows never need splits: their private writes start
        past any adopted prefix, and their pages only enter the prefix
        index at completion, so the coming chunk can never target a
        shared page.

        Grants go oldest-grant-first, splits after; when the pool
        cannot cover a decoding slot's top-up *or* its split, the slot
        *pauses* for the round (frozen row: emits nothing, its length
        rolls back after the dispatch, and its block-table row is
        sentinel-masked so the dispatch cannot write the still-shared
        page). A planned chunk whose pages starve is *deferred* (full
        chunk or nothing — partial advancement would break canonical
        chunk alignment), never partially advanced. If nobody can
        decode and no chunk can advance, the youngest grant is evicted
        back to the queue (eviction-safe: restart, not corruption)
        until someone can. Returns ``(paused_decode_slots,
        advancing_chunk_slots)``; some row always makes progress on
        return while any remain.
        """
        lazy = self.page_growth == "lazy"
        chunk_set = set(chunk_rows)
        if not self.active or (not lazy and not self.prefix_sharing):
            # eager growth pre-reserved every page at admission
            return set(), chunk_set
        C = max(self.prefill_chunk, 1)
        ps = self.pool.page_size
        lens = np.asarray(self.pool.lens)
        order = sorted(self.active, key=lambda s: self.active[s].rid)
        while order:
            decode_live = [s for s in order if not self._prefilling(s)]
            chunk_live = [s for s in order if s in chunk_set]
            # prefetch a lookahead window per grow acquire; fall back to
            # just-this-chunk when the pool is under the watermark so a
            # speculative grant never starves a must-have one
            tight = self.pool.pages.n_free <= self._headroom_pages()
            look = 1 if tight else _PAGE_LOOKAHEAD_CHUNKS
            items = []
            if lazy:
                for s in order:
                    if self._prefilling(s):
                        if s not in chunk_set:
                            # deferred backlog rows need no pages: their
                            # frozen decode-scan writes drop/overwrite
                            continue
                        target = min(int(self._pf_pos[s]) + C * look,
                                     int(self._pf_end[s]))
                    else:
                        target = int(min(lens[s] + steps * look,
                                         self._grow_cap[s]))
                    items.append((s, target))
            splits = (self._split_plan(decode_live, lens, steps)
                      if self.prefix_sharing else [])
            evict_groups: List[np.ndarray] = []
            if self.prefix_cache is not None and (items or splits):
                # watermark eviction rides THIS round's top-up acquire:
                # when the batch's grants would drag the free list
                # under the floor, LRU leaves cover the deficit (their
                # decrefs land before the grants, funding them)
                needed = sum(
                    max(self.pool.pages.pages_for(t)
                        - self.pool.held_pages(s), 0)
                    for s, t in items) + len(splits)
                if needed > 0:
                    deficit = (needed + self._watermark_pages()
                               - self.pool.pages.n_free)
                    evict_groups, _ = self._plan_evictions(deficit)
            try:
                _, split_ok = self.pool.prepare_batch(
                    items, splits, evict_groups=evict_groups)
            except InjectedFault:
                # aborted mid-batch: the pool's undo log rolled every
                # grant back. Re-apply the planned cache evictions (the
                # trie already forgot those pages) under suspended
                # injection, then pause every decoding row for the
                # round — frozen rows emit nothing and their lengths
                # roll back after the dispatch, so survivor streams
                # stay bit-identical and the top-ups retry next round.
                with self._faults_off():
                    self._free_batch_safe(evict_groups)
                self.rounds_retried += 1
                self.pauses += len(decode_live)
                return set(decode_live), set()
            self.cow_splits += sum(bool(ok) for ok in split_ok)
            # a slot pauses when it cannot cover THIS chunk (a denied
            # lookahead tail is not a reason to stall the row) or when
            # a split it needs starved — the shared page stays read-only
            paused = {
                s for s in decode_live
                if self.pool.held_pages(s) * ps
                < min(lens[s] + steps, self._grow_cap[s])}
            paused |= {s for (s, _), ok in zip(splits, split_ok) if not ok}
            starved = {
                s for s in chunk_live
                if self.pool.held_pages(s) * ps
                < min(int(self._pf_pos[s]) + C, int(self._pf_end[s]))}
            if not decode_live and not chunk_live:
                # nothing planned to advance — nothing to preempt for
                return paused, set()
            if len(paused) < len(decode_live) or len(starved) < len(
                    chunk_live):
                self.pauses += len(paused)
                return paused, chunk_set - starved
            # a lone slot can always grow (held + need <= max_pages_per_
            # slot <= num_pages) and never needs a split (refcount > 1
            # implies a second live holder), so preemption strictly
            # shrinks the starved set and the loop terminates. Victim
            # order is the SLO policy: rows past their deadline first
            # (evicting one expires it — §13), youngest grant otherwise.
            victim = max(order,
                         key=lambda s: (self._late(s), self.active[s].rid))
            self._preempt(victim)
            order.remove(victim)
            chunk_set.discard(victim)
            lens = np.asarray(self.pool.lens)
        return set(), set()

    # ------------------------------------------------------------ decode loop
    def step(self) -> int:
        """One scheduler round: apply round-boundary cancellations and
        queue-deadline expiries, re-tune the allocator's wait strategy
        from measured contention, admit the FIFO prefix the free slots
        hold (one batched page grant + prefix-adoption increfs), lazily
        top up active slots and apply any CoW splits (one batched
        grant/decref), then one fixed-shape decode dispatch of
        ``decode_chunk`` tokens, then retire finished rows (one batched
        decref/free — cancellation-deferred pages ride this same
        critical section). Returns the number of still-active
        requests."""
        with StepTraceAnnotation("serve.step", step_num=self.rounds):
            self.rounds += 1
            self._apply_cancels()
            self._expire_queued()
            with TraceAnnotation("serve.admit"):
                if self.kv_layout == "paged":
                    # between rounds, never mid-critical-section (the adaptive
                    # mutex contract); a no-op for pinned/auto wait modes
                    self.pool.retune()
                self._admit()
            if not self.active:
                self._flush_deferred_frees()
                return 0
            # round-level recovery (DESIGN.md §15): an injected fault rolls
            # the round back (the PRNG key is the only host state the
            # dispatch section had consumed) and retries with linear
            # backoff; repeated faults blamed on one request quarantine
            # exactly that request. The attempt cap bounds even an
            # always-faulting run: every failure advances some rid's
            # streak, so quarantines drain the active set before it trips.
            # Any other error — a compile error, device memory exhausted —
            # is not transient: it propagates to the caller untouched.
            attempts = 0
            max_attempts = self.quarantine_after * (len(self.active) + 1)
            while True:
                try:
                    n = self._run_round()
                except InjectedFault as exc:
                    attempts += 1
                    self.rounds_retried += 1
                    self._recover_round(exc)
                    if not self.active:
                        self._flush_deferred_frees()
                        return 0
                    if attempts >= max_attempts:
                        raise
                    if self.retry_backoff_s > 0:
                        time.sleep(self.retry_backoff_s * attempts)
                    continue
                self._round_failures.clear()
                return n

    def _run_round(self) -> int:
        """The round body ``step``'s recovery loop drives: plan, grow,
        dispatch, harvest, retire. Raises ``InjectedFault`` only from
        the dispatch section, which restores the PRNG key before
        re-raising — everything the section had not yet touched
        (lengths, cursors, block tables) is still the pre-round state,
        so a retry replays the round exactly."""
        steps = self.decode_chunk
        chunked = self.prefill_chunk > 0
        planned: List[int] = []
        if chunked:
            # token-budget round plan: decode rows first, then
            # fixed-size chunks for the FIFO-oldest prefilling slots —
            # except rows already past their deadline, which the
            # planner pushes behind every on-time row (they only chunk
            # on budget nobody on time could use; DESIGN.md §13)
            backlog = sorted(
                (s for s in self.active if self._prefilling(s)),
                key=lambda s: self.active[s].rid)
            decode_rows = [s for s in self.active
                           if not self._prefilling(s)]
            planned = plan_round(
                self.round_token_budget, decode_rows, backlog,
                chunk_tokens=self.prefill_chunk,
                decode_chunk=steps,
                deprioritized=[s for s in backlog
                               if self._late(s)],
                # charge each row its true remainder: a cache-shortened
                # prefill (cursor started past the adopted prefix) or a
                # final partial chunk never blocks budget another
                # backlog row could use
                remaining={s: int(self._pf_end[s] - self._pf_pos[s])
                           for s in backlog}).chunk_rows
        if self.kv_layout == "paged":
            with TraceAnnotation("serve.grow"):
                paused, advancing = self._grow_for_chunk(steps,
                                                         tuple(planned))
        else:
            paused, advancing = set(), set(planned)
        if not self.active:                    # everything preempted away
            self._flush_deferred_frees()
            return 0
        with TraceAnnotation("serve.dispatch"):
            chunk_rows = [s for s in planned
                          if s in advancing and s in self.active]
            pf_rows = ([s for s in self.active if self._prefilling(s)]
                       if chunked else [])
            frozen = np.ones(self.capacity, bool)
            for slot in self.active:
                if slot not in paused and not self._prefilling(slot):
                    frozen[slot] = False
            lens_before = np.asarray(self.pool.lens) if paused else None
            view = self.pool.cache_view()
            if paused:
                # paused rows must not touch the arena this round: masking
                # their block-table rows to sentinel drops their scatters
                # (in particular into a still-shared page whose CoW split
                # starved) and their frozen outputs never read anyway; the
                # rolled-back length makes the resumed chunk rewrite every
                # dropped position before its first read
                view["pages"] = self.pool.masked_table(paused)
            bucket_rows = None
            if self.bucketed_dispatch:
                # every active slot rides the bucket (prefilling/paused rows
                # included: the decode-scan-then-chunk-scatter ordering
                # invariant needs their lanes computed); vacant slots are
                # pure scratch and stay out, shrinking the dispatch
                kb = self._dispatch_cache.bucket(len(self.active))
                bucket_rows = jnp.asarray(self._dispatch_cache.pad_rows(
                    sorted(self.active), kb))
            # dispatch section: the PRNG split is the ONLY host state
            # consumed before the jitted call returns, so restoring the key
            # on failure rolls the whole section back — a retried round
            # replays with the same key and (under greedy decoding) the
            # same tokens
            key0 = self._key
            self._key, sub = jax.random.split(self._key)
            try:
                # an injected fault strikes before the round program is
                # called: the view is donated to that call, so only a
                # fault raised ahead of it leaves the arena intact for the
                # retried round
                if self.fault_plan is not None:
                    self.fault_plan.dispatch(
                        [r.rid for r in self.active.values()])
                if chunked:
                    C = self.prefill_chunk
                    pf_tok = np.zeros((self.capacity, C), np.int32)
                    pf_qpos = np.zeros((self.capacity, C), np.int32)
                    pf_wpos = np.full((self.capacity, C), _DROP_POS, np.int32)
                    valid: Dict[int, int] = {}
                    for s in chunk_rows:
                        p0 = int(self._pf_pos[s])
                        v = int(min(C, self._pf_end[s] - p0))
                        pf_tok[s, :v] = self.active[s].prompt[p0:p0 + v]
                        pf_qpos[s, :] = p0 + np.arange(C)
                        pf_wpos[s, :v] = p0 + np.arange(v)
                        valid[s] = v
                    if bucket_rows is not None:
                        cache, tok, toks, pf_logits = self._bucket_round(
                            self.params, view, bucket_rows,
                            jnp.asarray(self._last_tok), jnp.asarray(frozen),
                            jnp.asarray(pf_tok), jnp.asarray(pf_qpos),
                            jnp.asarray(pf_wpos), sub,
                            steps=steps, chunk=C if chunk_rows else 0)
                    else:
                        cache, tok, toks, pf_logits = self._round(
                            self.params, view,
                            jnp.asarray(self._last_tok), jnp.asarray(frozen),
                            jnp.asarray(pf_tok), jnp.asarray(pf_qpos),
                            jnp.asarray(pf_wpos), sub,
                            steps=steps, chunk=C if chunk_rows else 0)
                else:
                    if bucket_rows is not None:
                        cache, tok, toks = self._bucket_chunk(
                            self.params, view, bucket_rows,
                            jnp.asarray(self._last_tok), jnp.asarray(frozen),
                            sub, steps=steps)
                    else:
                        cache, tok, toks = self._chunk(
                            self.params, view,
                            jnp.asarray(self._last_tok), jnp.asarray(frozen),
                            sub, steps=steps)
                    pf_logits = None
            except InjectedFault:
                self._key = key0
                raise
        self.decode_dispatches += 1
        rows = (self.capacity if bucket_rows is None
                else int(bucket_rows.shape[0]))
        self.lanes_dispatched += rows * (
            steps + (self.prefill_chunk if chunk_rows else 0))
        # the host waits here for the round's results
        with TraceAnnotation("serve.wait"):
            self.pool.adopt(cache)
            self._last_tok = np.array(tok)  # writable copy (inserts mutate)
            toks = np.asarray(toks)                        # [steps, K]
        donated = {k: v for k, v in view.items() if k not in ("len", "pages")}
        self.arena_in_place += all(
            x.is_deleted() for x in jax.tree_util.tree_leaves(donated))

        with TraceAnnotation("serve.harvest"):
            # advance prefill cursors for the chunks that rode this dispatch
            completions: List[Tuple[int, int]] = []
            used = 0                  # lanes that computed something kept
            for s in chunk_rows:
                v = valid[s]
                used += v
                self._pf_pos[s] += v
                self.prefill_chunks += 1
                self.prefill_tokens += v
                self.pad_tokens += self.prefill_chunk - v
                self.active[s].prefill_chunks += 1
                if self._pf_pos[s] >= self._pf_end[s]:
                    completions.append((s, v))
            if paused or pf_rows:
                # roll lengths back: paused rows to before the dispatch,
                # prefilling rows to their cursor (the decode scan advanced
                # every row; its scratch writes for these rows land again —
                # identically or rewritten — before anything reads them, so
                # the length vector is the only state to rewind)
                lens = np.array(self.pool.lens)
                for s in pf_rows:
                    lens[s] = int(self._pf_pos[s])
                if paused:
                    idx = list(paused)
                    lens[idx] = lens_before[idx]
                self.pool.set_lens(jnp.asarray(lens))

            retire: List[Tuple[int, int]] = []
            pf_skip = set(pf_rows)
            for s, v in completions:
                # prompt fully cached: sample the first output token from
                # the chunk's last real lane — the prefilling → decoding
                # transition
                req = self.active[s]
                self._key, sub2 = jax.random.split(self._key)
                tok0 = int(self._sample(pf_logits[s, v - 1][None, :], sub2)[0])
                self._last_tok[s] = tok0
                req.out_tokens.append(tok0)
                if self.eos_id is not None and tok0 == self.eos_id:
                    req.eos = True
                if self.kv_layout == "paged" and self.prefix_sharing:
                    self.prefix_index.register(
                        req.prompt, 0,
                        self.pool.page_ids(
                            s,
                            self.pool.pages.pages_for(int(self._pf_end[s]))),
                        schedule=self.prefill_chunk)
                self._pf_pos[s] = 0
                self._pf_end[s] = 0
                req.state = RequestState.DECODING
                req.decode_start_step = self.step_clock
                if req.eos or self._steps_left[s] <= 0:
                    retire.append((s, 0))
            # live generated-boundary registration (§14): as a decoding
            # conversation crosses page boundaries, its prompt+reply full
            # pages enter the live index under the chunked key — a forked
            # request (same history, new continuation) adopts them while
            # the donor is still active. Chunked-key only (a fork's prompt
            # length differs, so one-shot buckets would never match), and
            # only with the cache on: its token-exactness contract (§14)
            # covers decode-written pages; plain §11 sharing keeps its
            # stricter bit-identical-by-construction tier.
            reg_gen = (self.prefix_cache is not None and self.prefix_sharing
                       and self.prefill_chunk > 0
                       and self.kv_layout == "paged")
            for slot in list(self.active):
                if slot in paused or slot in pf_skip:
                    continue
                req = self.active[slot]
                done_at = None
                for s in range(steps):
                    if self._steps_left[slot] <= 0:
                        break
                    t = int(toks[s, slot])
                    req.out_tokens.append(t)
                    used += 1
                    self._steps_left[slot] -= 1
                    if self.eos_id is not None and t == self.eos_id:
                        req.eos = True
                        done_at = s + 1
                        break
                    if self._steps_left[slot] <= 0:
                        done_at = s + 1
                if done_at is not None:
                    retire.append((slot, done_at))
                elif reg_gen:
                    ps = self.pool.page_size
                    extent = int(req.prompt.size) + len(req.out_tokens) - 1
                    n_full = extent // ps
                    if n_full > int(self._gen_reg[slot]):
                        written = np.concatenate(
                            [req.prompt,
                             np.asarray(req.out_tokens[:-1], np.int32)])
                        self.prefix_index.register(
                            written[:n_full * ps], 0,
                            self.pool.page_ids(slot, n_full),
                            schedule=self.prefill_chunk)
                        self._gen_reg[slot] = n_full
            self.lanes_used += used
        with TraceAnnotation("serve.retire"):
            self._retire_batch(retire)
        self.step_clock += steps
        return len(self.active)

    def run_until_done(self, max_rounds: int = 1_000_000) -> int:
        """Drain queue + active set. Returns scheduler rounds used."""
        rounds = 0
        while (self.queue or self.active) and rounds < max_rounds:
            self.step()
            rounds += 1
        return rounds

    def drop_prefix_cache(self) -> int:
        """Release every page the prefix cache retains (one
        ``free_batch``); returns how many references were dropped. The
        leak-check drain: after this, an idle engine's pool must be
        empty — benchmarks and the fuzz harness gate exactly that."""
        if self.prefix_cache is None:
            return 0
        groups = self.prefix_cache.drop_all()
        if groups:
            self._free_batch_safe(groups)
        return int(sum(g.size for g in groups))

    # -------------------------------------------------------------- reporting
    def stats(self) -> Dict[str, float]:
        """Aggregate serving counters. All values are floats; in runs
        without cancellations or deadlines every pre-existing key keeps
        its historical meaning (``finished`` counts FINISHED terminals,
        which is then every terminal). ``tokens`` counts every token
        actually delivered to a caller, including a cancelled request's
        partial stream. Wait/time-in-state percentiles are over granted
        terminal requests (an EXPIRED-in-queue request was never
        granted and has no wait to report)."""
        term = self.finished
        fin = [r for r in term if r.state is RequestState.FINISHED]
        granted = [r for r in term if r.grant_step >= 0]
        waits = np.asarray([r.wait_steps for r in granted], np.float32)
        waits_s = np.asarray([r.wait_s for r in granted], np.float32)
        toks = int(sum(len(r.out_tokens) for r in term))
        now_s = time.perf_counter()

        def pctl(vals, q):
            arr = np.asarray(vals, np.float32)
            return float(np.percentile(arr, q)) if arr.size else 0.0

        pf_steps = [r.prefill_steps for r in granted]
        dec_steps = [r.decode_steps for r in granted]
        q_steps = [r.queued_steps for r in granted]
        out = {
            "finished": float(len(fin)),
            "terminal": float(len(term)),
            "cancelled": float(self.cancellations),
            "expired": float(self.expiries),
            # fault-tolerance ledger (§15): all structurally zero in a
            # fault-free run
            "failed": float(sum(
                1 for r in term if r.state is RequestState.FAILED)),
            "faults_injected": float(
                self.fault_plan.injected if self.fault_plan else 0),
            "rounds_retried": float(self.rounds_retried),
            "requests_quarantined": float(self.requests_quarantined),
            "tokens": float(toks),
            "decode_dispatches": float(self.decode_dispatches),
            "p50_wait_steps": float(np.median(waits)) if len(granted)
            else 0.0,
            "p99_wait_steps": (float(np.percentile(waits, 99))
                               if len(granted) else 0.0),
            "p50_wait_s": (float(np.median(waits_s)) if len(granted)
                           else 0.0),
            "p99_wait_s": (float(np.percentile(waits_s, 99))
                           if len(granted) else 0.0),
            # time-in-state ledger (steps; queued + prefilling +
            # decoding partitions each granted request's lifetime)
            "queue_depth": float(len(self.queue)),
            "active_rows": float(len(self.active)),
            "p50_queued_steps": pctl(q_steps, 50),
            "p99_queued_steps": pctl(q_steps, 99),
            "p50_prefill_steps": pctl(pf_steps, 50),
            "p99_prefill_steps": pctl(pf_steps, 99),
            "p50_decode_steps": pctl(dec_steps, 50),
            "p99_decode_steps": pctl(dec_steps, 99),
            # deadline metadata for the in-flight slots (per-slot
            # detail via ``slot_deadlines()``)
            "deadline_rows": float(sum(
                1 for r in self.active.values()
                if r.deadline_step is not None or r.deadline_s is not None)),
            "late_rows": float(sum(
                r.past_deadline(self.step_clock, now_s)
                for r in self.active.values())),
            # chunked-prefill ledger (meaningful in both modes: one-shot
            # pads prompts to buckets, chunked pads only the last chunk)
            "prefill_chunk_tokens": float(self.prefill_chunk),
            "prefill_tokens": float(self.prefill_tokens),
            "pad_tokens": float(self.pad_tokens),
            "pad_fraction": (
                float(self.pad_tokens)
                / float(max(self.prefill_tokens + self.pad_tokens, 1))),
            "prefill_chunks": float(self.prefill_chunks),
            "decode_rounds_stalled_by_prefill": float(
                self.decode_rounds_stalled_by_prefill),
            # round-program lanes computed, and those kept (a decode
            # token, or a prompt position of a chunk)
            "lanes_dispatched": float(self.lanes_dispatched),
            "lanes_used": float(self.lanes_used),
            # share of round dispatches whose donated arena the round
            # program took over (1.0: updated in place, never copied)
            "arena_in_place": (float(self.arena_in_place)
                               / float(max(self.decode_dispatches, 1))),
        }
        # paged-attention read path + bucketed-dispatch ledger (§16):
        # retraces must be 0 in steady state — one trace per distinct
        # (bucket, steps, chunk) shape, a set bounded by log2(K)+1
        # buckets times the chunk ∈ {0, C} variants
        out.update({
            "attention_fused": float(self.attention_impl == "fused"),
            # 1.0: the gather reads (decode unless fused, and every
            # chunk) contracted against the KV heads, no expanded K/V
            "attention_kv_grouped": float(self.kv_grouped),
            "bucketed_dispatch": float(self.bucketed_dispatch),
            "dispatch_traces": float(
                self._dispatch_cache.traces
                if self._dispatch_cache is not None else 0),
            "dispatch_trace_keys": float(
                len(self._dispatch_cache.trace_keys)
                if self._dispatch_cache is not None else 0),
            "dispatch_retraces": float(
                self._dispatch_cache.retraces
                if self._dispatch_cache is not None else 0),
        })
        if self.kv_layout == "paged":
            pp = self.pool.pages
            ls = pp.lock_stats()
            out.update({
                "page_allocs": float(pp.allocs),
                "page_frees": float(pp.frees),
                "pages_peak_in_use": float(pp.peak_in_use),
                "pages_total": float(pp.num_pages),
                "page_pauses": float(self.pauses),
                "page_preemptions": float(self.preemptions),
                # the paper's currency: synchronizing ops on the
                # allocator per unit of useful work
                "lock_acquires": float(ls["acquires"]),
                "lock_contended_acquires": float(ls["contended_acquires"]),
                "lock_held_s": float(ls["held_s"]),
                "lock_acquires_per_token": (
                    float(ls["acquires"]) / float(max(toks, 1))),
                "lock_retunes": float(ls.get("retunes", 0)),
                "watchdog_trips": float(ls.get("watchdog_trips", 0)),
                "aborted_batches": float(pp.aborted_batches),
                # what a one-lock-per-page allocator (the PR 3 baseline
                # framing) would have paid for the same page traffic
                "per_page_lock_acquires_per_token": (
                    float(pp.pages_alloced + pp.pages_freed)
                    / float(max(toks, 1))),
                # prefix sharing's currency: physical page allocations
                # per served token (adoptions are increfs, not allocs)
                "pages_alloced": float(pp.pages_alloced),
                "pages_per_token": (float(pp.pages_alloced)
                                    / float(max(toks, 1))),
                "page_increfs": float(pp.increfs),
                "page_decrefs": float(pp.decrefs),
                "prefix_sharing": float(self.prefix_sharing),
                "prefix_hits": float(self.prefix_hits),
                "shared_pages_adopted": float(self.shared_pages_adopted),
                "cow_splits": float(self.cow_splits),
                # retained prefix cache (§14): hit/donation/eviction
                # ledger plus the compute actually saved (chunked-mode
                # prompt tokens never dispatched because the cursor
                # started past them on a cache adoption)
                "prefix_cache": float(self.prefix_cache is not None),
                "cache_hit_rate": (
                    float(self.prefix_cache.hits)
                    / float(max(self.prefix_cache.hits
                                + self.prefix_cache.misses, 1))
                    if self.prefix_cache is not None else 0.0),
                "cache_hits": float(self.cache_hits),
                "cache_tokens_served": float(self.cache_tokens_served),
                "prefill_tokens_saved": float(self.prefill_tokens_saved),
            })
            if self.prefix_cache is not None:
                out.update(self.prefix_cache.stats())
        return out

    def slot_deadlines(self) -> Dict[int, Dict[str, float]]:
        """Per-slot deadline metadata for the in-flight rows: the
        request id, its state, the absolute step deadline (-1 = none),
        steps of slack left on the step clock (negative once late), and
        whether the row is late right now. The scalar aggregates
        (``deadline_rows`` / ``late_rows``) live in :meth:`stats`."""
        now_s = time.perf_counter()
        out: Dict[int, Dict[str, float]] = {}
        for slot, req in sorted(self.active.items()):
            dl = req.deadline_step
            out[slot] = {
                "rid": float(req.rid),
                "state": req.state.value,
                "deadline_step": float(dl if dl is not None else -1),
                "slack_steps": (float(dl - self.step_clock)
                                if dl is not None else float("inf")),
                "late": bool(req.past_deadline(self.step_clock, now_s)),
            }
        return out
