"""Paged KV arena: a page-pool allocator + a block-table slot pool.

The contiguous slot arena (serve/kv_slots.py) reserves ``K * max_len``
tokens of KV up front — every slot pays for the longest context the
replica will ever serve. This module replaces that reservation with a
*paged* layout (ROADMAP "Paged attention"):

  * one ``[num_pages, page_size, ...]`` physical arena per cache-leaf
    family (each attention layer's k and v), shared by all K slots;
  * a per-slot *block table* — ``[K, max_pages_per_slot]`` int32 rows of
    page ids, sentinel-filled past the slot's allocation — mapping flat
    token positions to (page, offset) pairs;
  * ``PagePool`` — the O(1) FIFO free-list allocator those tables draw
    from. Page allocation/reclamation happen on the serve hot loop, so
    the allocator is gated by a ``repro.sync`` ticket-lock mutex — the
    paper's Algorithm-3 FA lock: one atomic to acquire, zero to release,
    FIFO-fair so a burst of admissions cannot starve a retirement — and
    every entry point is *batched*: one critical section per scheduler
    round covers a whole admission batch (``alloc_batch``), growth pass
    (``PagedSlotPool.grow_batch``), or retirement set (``free_batch``),
    so lock traffic is O(1) per round, not O(requests) or O(pages). The
    wait strategy comes from ``select_impl`` under the expected allocator
    contention, can be pinned per-arm (``wait_mode``), or adapts to the
    measured contended-acquire window (``wait_mode="adaptive"``,
    re-selected between rounds). See DESIGN.md §9-§10.

Copy-on-write prefix sharing (DESIGN.md §11) rides on three additions:

  * **per-page refcounts** in ``PagePool``: an allocation is born with
    refcount 1, adopting a page is ``incref_batch`` (or the
    ``incref_groups`` rider on ``alloc_batch`` — same critical section
    as the admission grant), and ``free_batch`` is a *decref*: a page
    returns to the FIFO free list only when its count hits zero. A
    per-page epoch (bumped at every grant) lets stale references be
    detected without holding the lock.
  * ``PrefixIndex`` — chained digests of a prompt's token prefix at
    every full-page boundary plus one entry for the partial tail, each
    pointing at the pages that hold that prefix's K/V. Admission does a
    longest-match lookup so a request whose prompt shares a prefix with
    a live request adopts those pages read-only instead of allocating
    and re-scattering them.
  * a **CoW split** primitive (``PagePool.alloc_batch(paired_decrefs=)``
    + ``PagedSlotPool.cow_split_batch``): the first write a slot aims at
    a page with refcount > 1 allocates a private copy, copies the page's
    contents in the arena, rewrites that slot's block-table entry, and
    drops the shared reference — all grants and decrefs under the one
    critical section the round's top-up pass already takes. The split
    invariant — *a shared page is never written; a written page has
    refcount 1* — is what keeps ``gather_pages`` readers oblivious:
    they never observe a partially-split page.

``PagedSlotPool`` is a drop-in for ``SlotPool`` (same
``acquire/insert/evict/cache_view/adopt/set_lens`` surface), so
``SlotServeEngine`` switches layouts with a constructor flag. Because
pages are granted on demand, one slot may hold a context *longer than
the contiguous layout's max_len* at equal arena bytes, as long as its
neighbours are short — the whole point of paging.

The decode path reads the paged cache through the gather helpers in
``models/attention.py`` (``gather_pages`` / ``scatter_page_token``); page
``j`` of a slot covers flat positions ``[j*ps, (j+1)*ps)``, so gathered
views stay in position order and reuse the contiguous masking.
"""

from __future__ import annotations

import collections
import hashlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.abstraction import PrimitiveKind, WaitStrategy
from repro.models.attention import copy_pages
from repro.serve.kv_slots import _split_len, batch_axes
from repro.sync import SyncLibrary

PyTree = Any


class PagePoolExhausted(RuntimeError):
    """alloc() asked for more pages than the free list holds."""


class PageLeakError(RuntimeError):
    """A refcount operation that would corrupt the arena's ownership.

    Decref-ing an already-free page (or one out of range, or more times
    in one batch than it holds references) would push a duplicate onto
    the FIFO free list, and the next two allocations would hand the
    *same physical page* to two slots — silent KV corruption discovered
    only when token streams diverge. Incref-ing a free page would
    resurrect a reference nobody owns. The allocator refuses atomically
    instead: every id in a batch is validated before any count moves.
    """


#: wait_mode name -> pinned ticket-lock wait strategy ("auto"/None defer
#: to ``select_impl``; "adaptive" re-selects from measured contention).
_WAIT_MODES = {
    "spin": WaitStrategy.SPIN,
    "spin_backoff": WaitStrategy.SPIN_BACKOFF,
    "sleeping": WaitStrategy.SLEEP,
}


class PagePool:
    """Fixed page arena bookkeeping: FIFO free list + per-page refcounts
    under one ticket mutex.

    The free list itself is trivially O(1); what matters (the paper's
    lesson) is how few synchronizing accesses each acquire of the
    guarding mutex needs. ``alloc_batch``/``free_batch``/``incref_batch``
    are the entry points and each takes the lock *once for a whole batch
    of requests*, so allocator lock traffic is O(1) per engine event
    (one critical section per scheduler round), not O(requests) — and
    never O(pages).
    ``grant_log`` records the tag of every granted request in lock-grant
    order — the ticket lock makes that order FIFO in ticket order, and a
    batch appends its grants in batch order, which the churn and
    equivalence tests pin.

    **Refcount protocol** (copy-on-write prefix sharing, DESIGN.md §11):
    a granted page starts at refcount 1; ``incref_batch`` adds a reader
    (prefix adoption); ``free_batch`` *decrefs* and only returns a page
    to the FIFO free list when its count hits zero — so a page shared by
    n slots is freed exactly once, by whichever holder drops the last
    reference. A per-page ``epoch`` is bumped at every grant;
    ``entry_valid`` checks a remembered (id, epoch) pair still names the
    same allocation, which is how the prefix index detects recycled
    pages without taking the lock. Callers that never incref see the
    exact pre-sharing semantics (every page lives at refcount 1).

    ``wait_mode`` picks how the allocator's waiters wait:

      * ``None``/``"auto"`` — the strategy ``select_impl`` derives from
        ``expected_contention`` (PR 3 behavior);
      * ``"spin"`` / ``"spin_backoff"`` / ``"sleeping"`` — pinned (the
        ``--alloc-sweep`` benchmark arms);
      * ``"adaptive"`` — a contention-adaptive ticket lock
        (``hostsync.AdaptiveMutex``) that re-selects its strategy from
        the measured contended-acquire fraction whenever the owner calls
        :meth:`retune` — between scheduler rounds, never mid-critical-
        section.
    """

    def __init__(self, num_pages: int, page_size: int, *,
                 sync: Optional[SyncLibrary] = None,
                 expected_contention: float = 0.25,
                 wait_mode: Optional[str] = None,
                 watchdog_s: Optional[float] = None):
        if num_pages < 1 or page_size < 1:
            raise ValueError("num_pages and page_size must be >= 1")
        if wait_mode not in (None, "auto", "adaptive", *_WAIT_MODES):
            raise ValueError(
                f"unknown wait_mode {wait_mode!r}; expected auto, adaptive, "
                f"or one of {sorted(_WAIT_MODES)}")
        self.num_pages = num_pages
        self.page_size = page_size
        self.sync = sync if sync is not None else SyncLibrary.host_default()
        self.choice = self.sync.choice(
            PrimitiveKind.MUTEX, expected_contention=expected_contention)
        self.wait_mode = wait_mode or "auto"
        # Algorithm-3 ticket lock; strategy per the machine abstraction's
        # read of the expected allocator contention unless pinned by
        # ``wait_mode`` or a library-level strategy pin — report
        # ``wait_strategy`` (below), not ``choice.strategy``, as what the
        # allocator actually runs right now.
        if self.wait_mode == "adaptive":
            self.mutex = self.sync.mutex(
                kind="adaptive", expected_contention=expected_contention)
        else:
            self.mutex = self.sync.mutex(
                kind="ticket", expected_contention=expected_contention,
                strategy=_WAIT_MODES.get(self.wait_mode))
        self._free = collections.deque(range(num_pages))
        self._allocated = np.zeros(num_pages, bool)
        self._refcount = np.zeros(num_pages, np.int32)
        self._epoch = np.zeros(num_pages, np.int64)   # bumped per grant
        self.allocs = 0          # granted requests (grant_log entries)
        self.frees = 0           # free events (one per returned group)
        self.pages_alloced = 0   # pages moved out of the free list
        self.pages_freed = 0     # pages moved back — with pages_alloced,
        #                          the "one lock per page" baseline ledger
        self.increfs = 0         # shared-adoption references added
        self.decrefs = 0         # references dropped (>= pages_freed)
        self.peak_in_use = 0
        self.grant_log: List[Any] = []
        # Fault surface (DESIGN.md §15): ``fault_hook(stage)`` is called
        # at named points *inside* the critical section; it may raise
        # (an injected mid-batch fault — the undo log rolls the batch
        # back atomically and re-raises) or stall (a stuck holder — the
        # mutex watchdog flags the over-threshold hold). None = no-op.
        self.fault_hook: Optional[Any] = None
        self.aborted_batches = 0
        if watchdog_s is not None:
            wd = getattr(self.mutex, "set_watchdog", None)
            if wd is not None:
                wd(watchdog_s)

    # ----------------------------------------------------------------- state
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def wait_strategy(self) -> WaitStrategy:
        """The wait strategy the allocator's mutex runs *right now*
        (adaptive mode re-selects it between scheduler rounds)."""
        s = getattr(self.mutex, "strategy", None)      # AdaptiveMutex
        if isinstance(s, WaitStrategy):
            return s
        return getattr(self.mutex, "_strategy",
                       self.sync.strategy or self.choice.strategy)

    def pages_for(self, tokens: int) -> int:
        """Pages needed to hold ``tokens`` flat positions."""
        return -(-max(int(tokens), 0) // self.page_size)

    # ------------------------------------------------------------- hot path
    def alloc_batch(self, counts: Sequence[int], tags: Optional[Sequence] = None,
                    *, partial: bool = False,
                    incref_groups: Optional[Sequence] = None,
                    paired_decrefs: Optional[Sequence] = None,
                    decref_groups: Optional[Sequence] = None
                    ) -> List[Optional[np.ndarray]]:
        """Grant a batch of page requests under ONE critical section.

        ``counts[i]`` pages go to request ``i`` (FIFO page-reuse order,
        requests granted in batch order). With ``partial=False`` the
        batch is all-or-nothing: :class:`PagePoolExhausted` is raised
        without granting anything when the total does not fit. With
        ``partial=True`` the FIFO *prefix* of requests that fits is
        granted and every request from the first unsatisfiable one on
        gets ``None`` — later (smaller) requests never leapfrog an
        earlier starved one, so growth stays starvation-free in request
        order. Each granted request appends its tag to ``grant_log``.

        Two refcount riders share the same critical section so a
        scheduler round's refcount traffic never costs an extra acquire:

          * ``incref_groups`` — page-id groups to incref after the
            grants (prefix adoptions of the same admission batch);
          * ``paired_decrefs`` — aligned with ``counts``: group ``i`` is
            decref'd **iff request i was granted** (a CoW split drops
            its shared reference only when the private copy's page was
            actually allocated). The CoW keeper rule (engine side)
            guarantees a split's source page retains at least one other
            reference, so the page a caller is about to copy from is
            never recycled by its own decref;
          * ``decref_groups`` — unconditional decrefs applied after the
            increfs but **before the grants**, so pages they free feed
            the same batch's allocations (the prefix cache's watermark
            eviction rides the round's existing top-up/admission
            acquire this way: the LRU leaves it drops fund the grants
            that demanded them).

        Failure is atomic for the whole call: increfs, paired decrefs
        (validated worst-case, as if every request were granted), and
        exhaustion are all checked before any count moves, so a raise
        leaves the pool untouched. Within the section the increfs land
        *before* the grants and decrefs — a rider that both increfs and
        paired-decrefs the same page nets out instead of transiently
        freeing it — and the grants pop the free list in the same FIFO
        order as a plain ``alloc_batch``.
        """
        counts = [int(n) for n in counts]
        if any(n < 0 for n in counts):
            raise ValueError("alloc of negative page count")
        if tags is None:
            tags = [None] * len(counts)
        if len(tags) != len(counts):
            raise ValueError("tags and counts length mismatch")
        if paired_decrefs is not None and len(paired_decrefs) != len(counts):
            raise ValueError("paired_decrefs and counts length mismatch")
        inc = [np.asarray(g, np.int32).reshape(-1)
               for g in (incref_groups or [])]
        paired = ([None if g is None
                   else np.asarray(g, np.int32).reshape(-1)
                   for g in paired_decrefs]
                  if paired_decrefs is not None else None)
        dec = [np.asarray(g, np.int32).reshape(-1)
               for g in (decref_groups or [])]
        out: List[Optional[np.ndarray]] = []
        with self.mutex:
            # validate everything before any count moves: a raise must
            # leave the pool exactly as it was (the atomic-failure
            # contract the per-call docs promise)
            for g in inc:
                self._check_incref(g)
            if paired is not None or dec:
                inc_count: Dict[int, int] = {}
                for g in inc:
                    for i in g.tolist():
                        inc_count[i] = inc_count.get(i, 0) + 1
                # one shared occurrence map across eviction + paired
                # decrefs: a page named by both riders must still not
                # exceed its (post-incref) reference total
                occ: Dict[int, int] = {}
                for g in dec:
                    for i in g.tolist():
                        if not (0 <= i < self.num_pages):
                            raise PageLeakError(
                                f"eviction decref of page {i} outside "
                                f"the arena [0, {self.num_pages})")
                        if not self._allocated[i]:
                            raise PageLeakError(
                                f"eviction decref of page {i} which is "
                                f"already free — a double-evict/donate "
                                f"race escaped the cache protocol")
                        occ[i] = occ.get(i, 0) + 1
                        if occ[i] > (int(self._refcount[i])
                                     + inc_count.get(i, 0)):
                            raise PageLeakError(
                                f"page {i} evicted beyond its held "
                                f"reference(s) — the extra decref would "
                                f"free a page someone still reads")
                for g in (paired or []):
                    for i in ([] if g is None else g.tolist()):
                        if not (0 <= i < self.num_pages):
                            raise PageLeakError(
                                f"paired decref of page {i} outside the "
                                f"arena [0, {self.num_pages})")
                        if not self._allocated[i]:
                            raise PageLeakError(
                                f"paired decref of page {i} which is "
                                f"already free")
                        occ[i] = occ.get(i, 0) + 1
                        if occ[i] > (int(self._refcount[i])
                                     + inc_count.get(i, 0)):
                            raise PageLeakError(
                                f"page {i} appears twice in one free "
                                f"batch beyond its references — even if "
                                f"every paired request were granted")
            # exhaustion credit for the eviction rider: only decrefs
            # that will actually free a page count — refcount 1 AND not
            # re-referenced by this same call's increfs (an adoption of
            # a page the eviction plan also names keeps it allocated)
            inc_pages = {i for g in inc for i in g.tolist()}
            if not partial and sum(counts) > len(self._free) + sum(
                    1 for g in dec for i in g.tolist()
                    if int(self._refcount[i]) == 1 and i not in inc_pages):
                raise PagePoolExhausted(
                    f"need {sum(counts)} pages, {len(self._free)} free of "
                    f"{self.num_pages}")
            # mutation phase — journaled so an injected mid-batch fault
            # (fault_hook raising at any stage) rolls everything applied
            # so far back in reverse and re-raises with the pool exactly
            # as it was: the undo-log extension of the validate-first
            # atomic-failure contract (DESIGN.md §15)
            undo: List[Any] = []
            try:
                self._fire("alloc:validated")
                # increfs land first: a rider that increfs and paired-
                # decrefs the same page nets out instead of transiently
                # freeing it under its new reader
                for g in inc:
                    self._refcount[g] += 1
                    self.increfs += int(g.size)
                    undo.append(self._undo_incref(g))
                self._fire("alloc:increfs")
                # eviction decrefs land before the grants: the pages
                # they return to the FIFO tail are available to this
                # very batch
                if dec:
                    self._decref_groups(dec, count_frees=True, undo=undo)
                self._fire("alloc:evict_decrefs")
                starved = False
                granted_decrefs = []
                for i, (n, tag) in enumerate(zip(counts, tags)):
                    if starved or n > len(self._free):
                        starved = True          # FIFO prefix only
                        out.append(None)
                        continue
                    ids = np.asarray(
                        [self._free.popleft() for _ in range(n)], np.int32)
                    self._allocated[ids] = True
                    self._refcount[ids] = 1
                    self._epoch[ids] += 1
                    self.allocs += 1
                    self.pages_alloced += n
                    self.grant_log.append(tag)
                    out.append(ids)
                    undo.append(self._undo_grant(ids, n))
                    if paired is not None and paired[i] is not None:
                        granted_decrefs.append(paired[i])
                    self._fire("alloc:grant")
                if granted_decrefs:
                    self._decref_groups(granted_decrefs, count_frees=False,
                                        undo=undo)
                self._fire("alloc:paired_decrefs")
            except BaseException:
                self._rollback(undo)
                raise
            self.peak_in_use = max(self.peak_in_use, self.in_use)
        return out

    # ------------------------------------------------------ fault injection
    def _fire(self, stage: str) -> None:
        """(Lock held.) Give the installed fault hook a shot at this
        mutation stage — it may raise (abort + rollback) or stall (the
        watchdog's stuck-holder case)."""
        if self.fault_hook is not None:
            self.fault_hook(stage)

    def _undo_incref(self, g: np.ndarray):
        def _undo():
            self._refcount[g] -= 1
            self.increfs -= int(g.size)
        return _undo

    def _undo_grant(self, ids: np.ndarray, n: int):
        def _undo():
            self.grant_log.pop()
            self.allocs -= 1
            self.pages_alloced -= n
            self._refcount[ids] = 0
            self._allocated[ids] = False
            self._epoch[ids] -= 1
            # the grant popped the free-list head; push back in reverse
            # so the FIFO order (and every later batch's grants) is
            # byte-identical to a never-faulted pool
            for p in reversed(ids.tolist()):
                self._free.appendleft(int(p))
        return _undo

    def _rollback(self, undo: List[Any]) -> None:
        """(Lock held.) Reverse every journaled mutation, newest first,
        and count the aborted batch. ``check()`` must pass afterwards —
        the transactional contract the fuzz suite audits."""
        for fn in reversed(undo):
            fn()
        self.aborted_batches += 1

    def alloc(self, n: int, tag: Any = None) -> np.ndarray:
        """Claim ``n`` pages (FIFO reuse order) — a batch of one. Raises
        :class:`PagePoolExhausted` without allocating when fewer than
        ``n`` are free — callers gate admission on ``n_free`` first."""
        return self.alloc_batch([n], [tag])[0]

    def _check_incref(self, g: np.ndarray) -> None:
        """(Lock held.) An incref must name live pages: resurrecting a
        free page would hand out a reference nobody owns."""
        for i in g.tolist():
            if not (0 <= i < self.num_pages):
                raise PageLeakError(
                    f"incref of page {i} outside the arena "
                    f"[0, {self.num_pages})")
            if not self._allocated[i]:
                raise PageLeakError(
                    f"incref of page {i} which is free — a reference to "
                    f"an unallocated page would alias the next grant")

    def _decref_groups(self, groups: List[np.ndarray],
                       count_frees: bool,
                       undo: Optional[List[Any]] = None) -> List[int]:
        """(Lock held.) Validate then apply a batch of decrefs; pages
        whose count hits zero return to the FIFO free-list tail in group
        order. Validation is atomic across the whole batch: every page's
        total occurrences must not exceed its refcount. When ``undo`` is
        given, a closure reversing the whole application is appended to
        it (the transactional-batch journal)."""
        occ: Dict[int, int] = {}
        for g in groups:
            for i in g.tolist():
                if not (0 <= i < self.num_pages):
                    raise PageLeakError(
                        f"freeing page {i} outside the arena "
                        f"[0, {self.num_pages})")
                if not self._allocated[i]:
                    raise PageLeakError(
                        f"freeing page {i} which is already free — "
                        f"double-free would duplicate it on the FIFO "
                        f"free list and alias two slots onto one page")
                occ[i] = occ.get(i, 0) + 1
                if occ[i] > int(self._refcount[i]):
                    raise PageLeakError(
                        f"page {i} appears twice in one free batch "
                        f"beyond its {int(self._refcount[i])} held "
                        f"reference(s) — the extra decref would free a "
                        f"page someone still reads")
        freed: List[int] = []
        applied: List[Tuple[int, bool]] = []   # (page, hit zero) in order
        for g in groups:
            n_freed = 0
            for i in g.tolist():
                self._refcount[i] -= 1
                self.decrefs += 1
                went_free = self._refcount[i] == 0
                applied.append((i, went_free))
                if went_free:
                    self._allocated[i] = False
                    self._free.append(i)
                    freed.append(i)
                    n_freed += 1
            if count_frees:
                self.frees += 1
            self.pages_freed += n_freed
        if undo is not None:
            n_groups, n_freed_total = len(groups), len(freed)

            def _undo():
                for i, went_free in reversed(applied):
                    if went_free:
                        back = self._free.pop()   # appended at the tail
                        assert back == i, "undo log out of sync"
                        self._allocated[i] = True
                    self._refcount[i] += 1
                    self.decrefs -= 1
                if count_frees:
                    self.frees -= n_groups
                self.pages_freed -= n_freed_total
            undo.append(_undo)
        return freed

    def incref_batch(self, groups: Sequence) -> None:
        """Add one reference to every page in every group under ONE
        critical section (prefix adoption: the new reader's admission).
        Validation is atomic across the batch: incref of a free or
        out-of-range page raises :class:`PageLeakError` with nothing
        applied. Admission batches normally ride the ``incref_groups``
        argument of :meth:`alloc_batch` instead, sharing the grant's
        critical section."""
        groups = [np.asarray(g, np.int32).reshape(-1) for g in groups]
        with self.mutex:
            for g in groups:
                self._check_incref(g)
            undo: List[Any] = []
            try:
                for g in groups:
                    self._refcount[g] += 1
                    self.increfs += int(g.size)
                    undo.append(self._undo_incref(g))
                self._fire("incref:applied")
            except BaseException:
                self._rollback(undo)
                raise

    def free_batch(self, groups: Sequence) -> List[int]:
        """Drop one reference per listed page under ONE critical section;
        return the ids actually freed (refcount hit zero).

        With prefix sharing off every page holds exactly one reference,
        so this is the classic batched free. With sharing on it is a
        *decref*: a page two slots adopted is returned to the free list
        exactly once — by the last holder. A page may appear in several
        groups of one batch (two adopters retiring in the same round);
        what is refused, atomically across the whole batch, is more
        occurrences than held references (:class:`PageLeakError` — a
        double-free). Each group counts as one free event (``frees``),
        mirroring ``alloc_batch``'s per-request grant accounting.
        """
        groups = [np.asarray(g, np.int32).reshape(-1) for g in groups]
        with self.mutex:
            undo: List[Any] = []
            try:
                self._fire("free:enter")
                freed = self._decref_groups(groups, count_frees=True,
                                            undo=undo)
                self._fire("free:decrefs")
            except BaseException:
                self._rollback(undo)
                raise
            return freed

    def free(self, ids) -> List[int]:
        """Drop one reference per page — a batch of one; returns the
        ids actually returned to the free list."""
        return self.free_batch([ids])

    # ------------------------------------------------------------ refcounts
    def refcounts(self, ids) -> np.ndarray:
        """Current reference counts (advisory snapshot, no lock — the
        serving engine is the only mutator between its own rounds)."""
        return self._refcount[np.asarray(ids, np.int32).reshape(-1)].copy()

    def epochs(self, ids) -> np.ndarray:
        """Per-page grant epochs for the given ids (bumped every time a
        page is granted, so a remembered (id, epoch) pair uniquely names
        one allocation's lifetime)."""
        return self._epoch[np.asarray(ids, np.int32).reshape(-1)].copy()

    def entry_valid(self, ids, epochs) -> bool:
        """True iff every (id, epoch) pair still names a live allocation
        — the prefix index's staleness probe (advisory, no lock)."""
        ids = np.asarray(ids, np.int32).reshape(-1)
        epochs = np.asarray(epochs, np.int64).reshape(-1)
        if ids.size == 0:
            return True
        if ids.min() < 0 or ids.max() >= self.num_pages:
            return False
        return (bool(self._allocated[ids].all())
                and bool((self._epoch[ids] == epochs).all()))

    # ----------------------------------------------------- contention signal
    def observed_contention(self) -> float:
        """Contended fraction of the allocator's recent lock acquires
        (sliding window kept by the instrumented host mutexes)."""
        fn = getattr(self.mutex, "recent_contention", None)
        return float(fn()) if fn is not None else 0.0

    def retune(self) -> Optional[WaitStrategy]:
        """Adaptive mode: re-select the wait strategy from the measured
        contention window. Call between scheduler rounds (never while
        the critical section is held by the caller). No-op — returns
        ``None`` — for pinned/auto modes."""
        retune = getattr(self.mutex, "retune", None)
        if retune is None:
            return None
        return retune(self.observed_contention())

    def reset_stats(self) -> None:
        """Zero allocation and lock counters (benchmarks reset after
        their warm phase; the free list itself is untouched)."""
        self.allocs = 0
        self.frees = 0
        self.pages_alloced = 0
        self.pages_freed = 0
        self.increfs = 0
        self.decrefs = 0
        self.aborted_batches = 0
        self.peak_in_use = self.in_use
        self.grant_log.clear()
        fn = getattr(self.mutex, "reset_stats", None)
        if fn is not None:
            fn()

    def lock_stats(self) -> dict:
        """Acquire/contended-acquire/held-time counters of the guarding
        mutex, plus the strategy currently in effect."""
        fn = getattr(self.mutex, "lock_stats", None)
        st = dict(fn()) if fn is not None else {}
        st.setdefault("acquires", 0)
        st.setdefault("contended_acquires", 0)
        st.setdefault("held_s", 0.0)
        st["strategy"] = self.wait_strategy.value
        st["wait_mode"] = self.wait_mode
        return st

    # ------------------------------------------------------------ invariants
    def check(self) -> None:
        """Free list, allocation bitmap, and refcounts tell one story:
        the free list and the allocated set partition the arena, and a
        page is allocated iff it holds at least one reference."""
        free = list(self._free)
        assert len(set(free)) == len(free), "duplicate page on free list"
        assert not self._allocated[free].any(), "free page marked allocated"
        assert int(self._allocated.sum()) + len(free) == self.num_pages, \
            "pages leaked: allocated + free != arena"
        assert ((self._refcount > 0) == self._allocated).all(), \
            "refcounts disagree with the allocation bitmap"


class PrefixIndex:
    """Longest-prefix-match index from prompt tokens to live KV pages.

    One entry per *registered prefix length*: every full-page boundary
    of an admitted prompt, plus one entry for the partial tail (the
    page that holds the prompt's last ``len % page_size`` positions).
    The key is a chained ``blake2b`` digest of the token prefix — the
    chain means looking up a prompt's boundary ``j`` costs O(page_size)
    incremental hashing, not O(j * page_size) — suffixed with the
    prefill bucket (see below). Values are ``(page_ids, epochs)``: the
    pages holding that prefix's K/V, pinned to their allocation epoch
    so a recycled page invalidates the entry (``PagePool.entry_valid``)
    instead of aliasing unrelated data. Stale entries are pruned lazily
    at lookup; nothing in the index holds a reference — adoption increfs
    under the admission critical section, the index is pure advice.

    Partial-tail entries chain a marker byte into the digest, so they
    can only match a prompt of *exactly* the registered length: a
    longer prompt would have to write its continuation into the shared
    page (a write to a refcount>1 page at admission time), which the
    protocol forbids — such prompts fall back to the longest full-page
    boundary match and scatter their own tail page.

    **Why the bucket suffix:** adopted pages are read in place of pages
    the adopter would have scattered from its own prefill. Token
    streams must be *bit-identical* with sharing on or off (the
    cross-layout fingerprint contract), and XLA only guarantees
    bitwise-reproducible K/V for the shared positions when the donor's
    prefill ran at the same padded shape — same bucket, causal masking
    does the rest (position ``i``'s K/V depends only on tokens ``<= i``
    plus exact zeros from the pad mask). Keying on the bucket restricts
    matches to donors whose prefill was shape-identical, making
    bit-equality structural rather than hopeful.
    """

    def __init__(self, page_size: int, pool: PagePool):
        self.page_size = int(page_size)
        self.pool = pool
        self._entries: Dict[bytes, Tuple[np.ndarray, np.ndarray]] = {}
        self.hits = 0            # lookups that adopted at least one page
        self.misses = 0
        self.pruned = 0          # stale entries dropped at lookup

    def __len__(self) -> int:
        return len(self._entries)

    def _digests(self, tokens: np.ndarray) -> List[Tuple[int, bytes]]:
        """(prefix_len, digest) per full-page boundary, ascending, plus
        the marker-chained partial tail when the length is unaligned."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        ps = self.page_size
        h = hashlib.blake2b(digest_size=16)
        out: List[Tuple[int, bytes]] = []
        n_full = tokens.size // ps
        for j in range(n_full):
            h.update(tokens[j * ps:(j + 1) * ps].tobytes())
            out.append(((j + 1) * ps, h.copy().digest()))
        tail = tokens.size - n_full * ps
        if tail:
            h.update(b"\x00partial")
            h.update(tokens[n_full * ps:].tobytes())
            out.append((tokens.size, h.digest()))
        return out

    @staticmethod
    def _key(digest: bytes, bucket: int, schedule: int = 0) -> bytes:
        # ``schedule`` extends the shape-identity suffix for chunked
        # prefill: 0 = one-shot (bucketed) prefill, C = chunked at C
        # tokens per chunk. Chunk boundaries are canonical multiples of
        # C, so two prompts prefilled at the same C compute
        # bit-identical K/V for a shared prefix — but a chunked donor's
        # bits are NOT the one-shot bits (different attention
        # reduction), so the two schedules must never cross-adopt.
        return (digest + int(bucket).to_bytes(4, "little")
                + int(schedule).to_bytes(4, "little"))

    def register(self, tokens, bucket: int, page_ids,
                 schedule: int = 0) -> int:
        """Publish a freshly inserted prompt's prefixes. ``page_ids``
        are the slot's table entries covering the prompt (shared pages
        it adopted followed by its own — both are valid donors, which is
        what makes sharing transitive: an adopter can donate to a third
        request after the original donor retires). A key whose current
        entry is still live is kept (earliest donor stays canonical);
        dead entries are overwritten. Returns entries (re)written."""
        page_ids = np.asarray(page_ids, np.int32).reshape(-1)
        ps = self.page_size
        written = 0
        for length, digest in self._digests(tokens):
            n = -(-length // ps)
            if n > page_ids.size:
                break
            key = self._key(digest, bucket, schedule)
            cur = self._entries.get(key)
            if cur is not None and self.pool.entry_valid(cur[0], cur[1]):
                continue
            ids = page_ids[:n].copy()
            self._entries[key] = (ids, self.pool.epochs(ids))
            written += 1
        return written

    def lookup(self, tokens, bucket: int, schedule: int = 0
               ) -> Tuple[int, Optional[np.ndarray]]:
        """Longest live match: ``(shared_len, page_ids)`` such that the
        first ``shared_len`` positions of ``tokens`` are already held in
        ``page_ids`` by some live request, or ``(0, None)``. The caller
        must incref the returned pages (under its admission critical
        section) before anything else can retire the donor."""
        for length, digest in reversed(self._digests(tokens)):
            key = self._key(digest, bucket, schedule)
            ent = self._entries.get(key)
            if ent is None:
                continue
            ids, epochs = ent
            if not self.pool.entry_valid(ids, epochs):
                del self._entries[key]
                self.pruned += 1
                continue
            self.hits += 1
            return length, ids.copy()
        self.misses += 1
        return 0, None


class PagedSlotPool:
    """Block-table KV pool satisfying the ``SlotPool`` engine surface.

    ``max_len`` keeps its contiguous-layout meaning of *arena sizing*:
    the default page budget is ``ceil(K * max_len / page_size)`` — equal
    arena bytes — but any single slot may grow to
    ``max_pages_per_slot * page_size`` tokens (``virtual_max_len``).
    That bound also sizes the per-row gathered attention view, so it
    defaults to two slot rows (``ceil(2 * max_len / page_size)``): long
    contexts at near-contiguous decode cost. Passing
    ``max_pages_per_slot`` explicitly (up to ``num_pages``) trades
    gather width for longer contexts.

    Leaves named ``k``/``v`` (time-axis caches) are paged; every other
    leaf (mamba conv/h state — no time axis) stays slot-dense exactly as
    in ``SlotPool``, using the same detected batch axes.

    Under copy-on-write prefix sharing (DESIGN.md §11) one page may sit
    in several slots' block tables at once — the pool's :meth:`check`
    invariant becomes "every allocated page is mapped by exactly
    ``refcount`` rows". The sharing surface is: ``reserve_batch(shared=)``
    / ``insert(shared_ids=, shared_len=)`` for adoption,
    ``shared_write_targets`` + ``prepare_batch(split_items)`` for the
    CoW splits, and ``masked_table`` for pausing a row without letting
    it write. Eviction needs no sharing awareness at all: ``free_batch``
    decrefs, and the last holder's retirement frees the page.
    """

    def __init__(self, model, capacity: int, max_len: int, *,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 max_pages_per_slot: Optional[int] = None,
                 sync: Optional[SyncLibrary] = None,
                 expected_contention: float = 0.25,
                 wait_mode: Optional[str] = None,
                 watchdog_s: Optional[float] = None):
        if capacity < 1:
            raise ValueError("slot pool capacity must be >= 1")
        self.capacity = capacity
        self.max_len = max_len
        self.page_size = page_size
        if num_pages is None:
            num_pages = -(-capacity * max_len // page_size)
        self.pages = PagePool(num_pages, page_size, sync=sync,
                              expected_contention=expected_contention,
                              wait_mode=wait_mode,
                              watchdog_s=watchdog_s)
        if max_pages_per_slot is None:
            max_pages_per_slot = -(-2 * max_len // page_size)
        self.max_pages_per_slot = min(max_pages_per_slot, num_pages)

        self._axes = batch_axes(model, max_len)
        shapes, _ = _split_len(
            model.init_cache(capacity, max_len, for_shapes=True))
        self._treedef = jax.tree_util.tree_structure(shapes)
        paths = jax.tree_util.tree_flatten_with_path(shapes)[0]
        self._paged: List[bool] = []
        leaves = []
        for (path, leaf), ax in zip(paths, self._axes):
            key = getattr(path[-1], "key", None)
            paged = key in ("k", "v")
            self._paged.append(paged)
            if paged:
                if leaf.shape[ax] != capacity or leaf.shape[ax + 1] != max_len:
                    raise ValueError(
                        f"k/v leaf {leaf.shape} lacks [batch, time] at "
                        f"axes ({ax}, {ax + 1})")
                shape = (leaf.shape[:ax] + (num_pages, page_size)
                         + leaf.shape[ax + 2:])
            else:
                shape = leaf.shape
            leaves.append(jnp.zeros(shape, leaf.dtype))
        self.arena: PyTree = jax.tree_util.tree_unflatten(
            self._treedef, leaves)

        self.lens: jax.Array = jnp.zeros((capacity,), jnp.int32)
        # sentinel = num_pages: gathers clip it, scattered writes drop it
        self._tables = np.full((capacity, self.max_pages_per_slot),
                               num_pages, np.int32)
        self._free: List[int] = list(range(capacity))
        self._rid: List[Optional[int]] = [None] * capacity
        self._external_holders: List[Any] = []
        self._insert_jit = jax.jit(self._insert_impl,
                                   static_argnames=("skip",))

    # ------------------------------------------------------------- free list
    @property
    def virtual_max_len(self) -> int:
        """Longest context one slot can hold — decoupled from ``max_len``
        (which only sizes the arena): the paged layout's whole point."""
        return self.max_pages_per_slot * self.page_size

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return self.capacity - len(self._free)

    def active_slots(self) -> List[int]:
        return [s for s, r in enumerate(self._rid) if r is not None]

    def rid_of(self, slot: int) -> Optional[int]:
        return self._rid[slot]

    def acquire(self, rid: int) -> int:
        """Claim the next free slot (FIFO reuse order) for request rid."""
        if not self._free:
            raise RuntimeError("slot pool exhausted — admission must gate "
                               "on the semaphore before acquiring")
        slot = self._free.pop(0)
        self._rid[slot] = rid
        return slot

    def evict(self, slot: int, *, free_pages: bool = True
              ) -> Optional[np.ndarray]:
        """Retire a slot and reset its table row to sentinel.

        ``free_pages=True`` reclaims its pages immediately (one allocator
        critical section). ``free_pages=False`` *defers* the reclaim and
        returns the held page ids instead — the engine collects a whole
        scheduler round's retirements and returns them in one
        ``pages.free_batch`` critical section (the batched-free half of
        the O(1)-lock-traffic contract). Shared (prefix-adopted) pages
        need no special casing on either path: the free is a decref, so
        a page this slot shared with a live adopter survives until the
        last holder retires."""
        if self._rid[slot] is None:
            raise RuntimeError(f"evicting free slot {slot}")
        held = self._tables[slot][self._tables[slot] < self.pages.num_pages]
        self._tables[slot] = self.pages.num_pages
        self._rid[slot] = None
        self._free.append(slot)
        if free_pages:
            if held.size:
                self.pages.free(held)
            return None
        return held

    # ------------------------------------------------------------- admission
    def can_reserve(self, tokens: int, pending_pages: int = 0,
                    shared_pages: int = 0, extra_free: int = 0) -> bool:
        """Whether an insert reserving ``tokens`` flat positions can be
        satisfied right now (admission gates on this *before* taking the
        slot semaphore, so head-of-line blocking stays FIFO).
        ``pending_pages`` accounts for grants already staged in the same
        admission batch but not yet allocated; ``shared_pages`` are
        prefix-adopted pages the request will incref instead of
        allocate — they count toward the per-slot table bound but cost
        nothing from the free list. ``extra_free`` credits pages a
        planned cache eviction will return inside the same upcoming
        critical section (they are not on the free list *yet*)."""
        n = self.pages.pages_for(tokens)
        need_now = max(n - max(int(shared_pages), 0), 0)
        return (n <= self.max_pages_per_slot
                and need_now + max(int(pending_pages), 0)
                <= self.pages.n_free + max(int(extra_free), 0))

    def can_admit_lazy(self, initial_tokens: int, total_tokens: int,
                       headroom_pages: int = 0,
                       pending_pages: int = 0,
                       shared_pages: int = 0,
                       extra_free: int = 0) -> bool:
        """Lazy-growth admission gate: only the *initial* grant (the
        prefill bucket) must fit now, plus a configurable headroom so
        admissions do not starve in-flight slots' top-ups; the
        worst-case ``total_tokens`` only has to respect the per-slot
        page bound (it is never reserved up front). ``pending_pages``
        accounts for grants staged earlier in the same admission batch;
        ``shared_pages`` are prefix-adopted pages (increfs, free for
        the free-list's purposes — but still bound by the table width).
        An empty pool (nothing active, nothing staged) waives the
        headroom — the sole request always fits by the per-slot bound
        and waiting would deadlock."""
        need_total = self.pages.pages_for(total_tokens)
        if need_total > self.max_pages_per_slot:
            return False
        need_now = (max(self.pages.pages_for(initial_tokens)
                        - max(int(shared_pages), 0), 0)
                    + max(int(pending_pages), 0))
        avail = self.pages.n_free + max(int(extra_free), 0)
        if self.n_active == 0 and pending_pages == 0:
            return need_now <= avail
        return need_now + max(int(headroom_pages), 0) <= avail

    def held_pages(self, slot: int) -> int:
        """Pages currently mapped by ``slot``'s block table."""
        return int((self._tables[slot] < self.pages.num_pages).sum())

    def page_ids(self, slot: int, n: Optional[int] = None) -> np.ndarray:
        """The first ``n`` (default: all) real page ids of ``slot``'s
        block table, in flat-position order — what the prefix index
        registers as a prompt's K/V home."""
        row = self._tables[slot]
        real = row[row < self.pages.num_pages]
        return (real if n is None else real[:n]).copy()

    def masked_table(self, slots) -> jnp.ndarray:
        """The block table with the given slots' rows sentinel-masked —
        handed to a dispatch in place of ``cache_view()['pages']`` so
        paused rows can neither write their pages (scatters drop at the
        sentinel) nor depend on reads (their outputs are frozen and
        their lengths roll back). This is what keeps a slot whose CoW
        split starved from ever writing the still-shared page."""
        tbl = self._tables.copy()
        idx = list(slots)
        if idx:
            tbl[idx] = self.pages.num_pages
        return jnp.asarray(tbl)

    def grow_batch(self, items: Sequence[Tuple[int, int]]) -> List[bool]:
        """Top up several slots to cover ``need_tokens`` flat positions
        each, under ONE allocator critical section.

        ``items`` is ``[(slot, need_tokens), ...]`` in the engine's FIFO
        (oldest-grant-first) order; the allocator grants the FIFO prefix
        that fits (``alloc_batch(partial=True)``), so a starved old slot
        is never leapfrogged by a younger one. Returns one bool per
        item: True when the slot now covers ``need_tokens`` (including
        "already did"), False when its top-up must wait for reclaimed
        pages. Raises when a slot would outgrow ``max_pages_per_slot`` —
        callers cap their need at the insert-time reserve, which
        admission already bounded. A round that also needs CoW splits
        should call :meth:`prepare_batch` so both ride one acquire.
        """
        ok, _ = self.prepare_batch(items, [])
        return ok

    def shared_write_targets(self, slot: int, start_pos: int,
                             end_pos: int) -> List[Tuple[int, int]]:
        """``(table_idx, page_id)`` of the pages ``slot`` would write in
        flat positions ``[start_pos, end_pos)`` that are currently
        *shared* (refcount > 1) — the pages the split invariant says
        must be copied (or the write withheld) before the dispatch.
        Indices past the slot's held pages are ignored: an unallocated
        tail is a growth concern, not a sharing one."""
        if end_pos <= start_pos:
            return []
        ps = self.page_size
        held = self.held_pages(slot)
        lo = max(start_pos // ps, 0)
        hi = min((end_pos - 1) // ps, held - 1)
        if hi < lo:
            return []
        idxs = list(range(lo, hi + 1))
        pages = self._tables[slot, idxs]
        rc = self.pages.refcounts(pages)
        return [(j, int(p)) for j, p, r in zip(idxs, pages, rc)
                if int(r) > 1]

    def prepare_batch(self, grow_items: Sequence[Tuple[int, int]],
                      split_items: Sequence[Tuple[int, int]],
                      evict_groups: Sequence = ()
                      ) -> Tuple[List[bool], List[bool]]:
        """One critical section for a scheduler round's page prep: lazy
        top-ups plus copy-on-write splits.

        ``grow_items`` is ``[(slot, need_tokens), ...]`` exactly as
        :meth:`grow_batch`; ``split_items`` is ``[(slot, table_idx),
        ...]`` — pages whose coming write targets a shared (refcount>1)
        page, as found by :meth:`shared_write_targets`. Every split is
        granted one private page whose shared source is decref'd *in
        the same critical section* (``alloc_batch(paired_decrefs=)``),
        then the page contents are copied in the arena and the slot's
        table entry is repointed — so the round's whole prep costs one
        lock acquire whether or not any request is sharing. The split's
        source page always survives its own decref (the engine's keeper
        rule leaves at least one other holder), so the copy reads a
        live page. Grants are FIFO-prefix partial: grows (oldest first)
        then splits; a starved split means that slot must pause —
        writing the shared page is never an option.

        ``evict_groups`` (page-id groups) are prefix-cache LRU leaves
        dropped under the same acquire, *before* the grants — the §10
        ledger's "eviction rides the top-up section" row.

        Returns ``(grow_ok, split_ok)`` aligned with the inputs.
        """
        plan = []                     # (idx, slot, held, extra)
        grow_ok = [True] * len(grow_items)
        for idx, (slot, need_tokens) in enumerate(grow_items):
            if self._rid[slot] is None:
                raise RuntimeError(f"growing free slot {slot}")
            need = self.pages.pages_for(need_tokens)
            if need > self.max_pages_per_slot:
                raise ValueError(
                    f"slot {slot} growth to {need_tokens} tokens needs "
                    f"{need} pages > max_pages_per_slot "
                    f"{self.max_pages_per_slot}")
            held = self.held_pages(slot)
            if need > held:
                plan.append((idx, slot, held, need - held))
        split_old = [int(self._tables[slot, j]) for slot, j in split_items]
        if not plan and not split_items:
            if evict_groups:
                # nothing to grant but planned evictions MUST land (the
                # cache already forgot these pages) — still one acquire
                self.pages.free_batch(evict_groups)
            return grow_ok, []
        counts = ([extra for (_, _, _, extra) in plan]
                  + [1] * len(split_items))
        tags = ([self._rid[slot] for (_, slot, _, _) in plan]
                + [("cow", self._rid[slot]) for slot, _ in split_items])
        paired = ([None] * len(plan)
                  + [[old] for old in split_old])
        grants = self.pages.alloc_batch(counts, tags, partial=True,
                                        paired_decrefs=paired,
                                        decref_groups=evict_groups or None)
        for (idx, slot, held, _), ids in zip(plan, grants):
            if ids is None:
                grow_ok[idx] = False
                continue
            self._tables[slot, held:held + ids.size] = ids
        split_grants = grants[len(plan):]
        src = [old for old, ids in zip(split_old, split_grants)
               if ids is not None]
        dst = [int(ids[0]) for ids in split_grants if ids is not None]
        if src:
            self._copy_arena_pages(np.asarray(src, np.int32),
                                   np.asarray(dst, np.int32))
        split_ok = []
        for (slot, j), ids in zip(split_items, split_grants):
            if ids is None:
                split_ok.append(False)
                continue
            self._tables[slot, j] = int(ids[0])
            split_ok.append(True)
        return grow_ok, split_ok

    def _copy_arena_pages(self, src: np.ndarray, dst: np.ndarray) -> None:
        """Device half of the CoW split: copy pages ``src[i] -> dst[i]``
        in every paged leaf family (attention.copy_pages on each k/v
        arena; dense leaves have no page axis and are untouched)."""
        s, d = jnp.asarray(src), jnp.asarray(dst)
        leaves = jax.tree_util.tree_leaves(self.arena)
        out = [copy_pages(a, s, d, axis=ax) if paged else a
               for a, ax, paged in zip(leaves, self._axes, self._paged)]
        self.arena = jax.tree_util.tree_unflatten(self._treedef, out)

    # --------------------------------------------------------------- device
    def _insert_impl(self, arena, lens, req, ids, slot, length, *,
                     skip: int = 0):
        # ``skip`` (static) is the count of prefix-adopted pages at the
        # head of the slot's table: the request's first ``skip*ps`` flat
        # positions live in shared pages this scatter must never touch
        # (the split invariant), so the prefill data is sliced past them
        # and only the private remainder lands in ``ids``.
        la = jax.tree_util.tree_leaves(arena)
        lr = jax.tree_util.tree_leaves(req)
        n_data = ids.shape[0]
        out = []
        for a, r, ax, paged in zip(la, lr, self._axes, self._paged):
            if not paged:
                out.append(jax.lax.dynamic_update_slice_in_dim(
                    a, r.astype(a.dtype), slot, axis=ax))
                continue
            if n_data == 0:
                out.append(a)            # fully shared prefill: no write
                continue
            ps = a.shape[ax + 1]
            r = jnp.squeeze(r, axis=ax)              # drop batch-1; time at ax
            s = r.shape[ax]
            start = min(skip * ps, s)
            if start:
                r = jax.lax.slice_in_dim(r, start, s, axis=ax)
            sl = s - start
            pad = [(0, 0)] * r.ndim
            pad[ax] = (0, n_data * ps - sl)
            r = jnp.pad(r, pad).reshape(
                r.shape[:ax] + (n_data, ps) + r.shape[ax + 1:])
            idx = (slice(None),) * ax + (ids,)
            out.append(a.at[idx].set(r.astype(a.dtype)))
        return (jax.tree_util.tree_unflatten(self._treedef, out),
                lens.at[slot].set(length))

    def reserve_batch(self, items: Sequence[Tuple[int, int]],
                      shared: Optional[Sequence] = None,
                      evict: Optional[Sequence] = None
                      ) -> List[np.ndarray]:
        """Pre-grant ``[(slot, reserve_tokens), ...]`` in ONE allocator
        critical section, for handing to :meth:`insert` via ``ids=``.
        All-or-nothing (admission already gated on the pool state); the
        grant log gets one entry per request, in batch order — exactly
        what a per-request ``alloc`` loop would have produced, minus the
        per-request lock acquisitions.

        ``shared`` (aligned with ``items``, entries ``None`` or a page-id
        array) lists each request's prefix-adopted pages: their count is
        deducted from the request's grant and they are *incref'd under
        the same critical section* (``alloc_batch(incref_groups=)``), so
        an admission batch costs one acquire with or without sharing —
        and a fully-shared prompt's "allocation" is pure refcounting.

        ``evict`` (page-id groups) are prefix-cache LRU leaves whose
        references are dropped under the same critical section, before
        the grants — watermark eviction rides the admission acquire
        and its freed pages fund this very batch.
        """
        counts, incref_groups = [], []
        for i, (slot, tokens) in enumerate(items):
            n = self.pages.pages_for(tokens)
            if n > self.max_pages_per_slot:
                raise ValueError(
                    f"reserve {tokens} needs {n} pages > "
                    f"max_pages_per_slot {self.max_pages_per_slot}")
            sh = shared[i] if shared is not None else None
            n_sh = 0 if sh is None else int(np.asarray(sh).size)
            if n_sh:
                incref_groups.append(np.asarray(sh, np.int32).reshape(-1))
            counts.append(max(n - n_sh, 0))
        return self.pages.alloc_batch(
            counts, [self._rid[slot] for slot, _ in items],
            incref_groups=incref_groups or None,
            decref_groups=evict or None)

    def insert(self, slot: int, req_cache: PyTree, length,
               reserve: Optional[int] = None,
               ids: Optional[np.ndarray] = None,
               shared_ids: Optional[np.ndarray] = None,
               shared_len: int = 0) -> None:
        """Scatter a prefilled batch-1 request cache into ``slot``'s
        pages.

        ``reserve`` is the flat positions claimed *at insert*: the
        worst-case total (prompt + generation) under eager growth — so
        decode never allocates mid-dispatch — or just the prefill bucket
        under lazy growth, whose top-ups arrive per decode chunk via
        :meth:`grow_batch`. When omitted it defaults to a full
        ``max_len`` row — the contiguous layout's guarantee, so
        SlotPool-style callers can never silently outgrow their pages.
        ``ids`` hands in pages pre-granted by :meth:`reserve_batch`
        (one critical section for a whole admission batch); when absent
        the insert allocates its own (one critical section).

        ``shared_ids``/``shared_len`` are a prefix adoption (already
        incref'd by ``reserve_batch(shared=...)``): the pages holding
        the request's first ``shared_len`` flat positions, placed at the
        head of the slot's block table and **excluded from the
        scatter** — a shared page is never written, so the prefill data
        for those positions is simply dropped (it is bit-identical to
        what the donor already wrote, by the prefix index's same-bucket
        rule). Private prefill data covers pages ``n_shared ..
        ceil(S/ps)-1``; any remainder holds stale bytes masked by the
        length vector until decode writes them.
        """
        lr = jax.tree_util.tree_leaves(_split_len(req_cache)[0])
        s = 0
        for leaf, ax, paged in zip(lr, self._axes, self._paged):
            if paged:
                s = leaf.shape[ax + 1]
                break
        if shared_ids is None:
            shared_ids = np.zeros(0, np.int32)
        shared_ids = np.asarray(shared_ids, np.int32).reshape(-1)
        n_shared = int(shared_ids.size)
        if n_shared and not (0 < shared_len <= int(length)):
            raise ValueError(
                f"shared_len {shared_len} must cover (0, length] — the "
                f"adopted prefix is part of this request's prompt")
        reserve = max(int(reserve) if reserve is not None else self.max_len,
                      s, int(length))
        n_total = self.pages.pages_for(reserve)
        if n_total > self.max_pages_per_slot:
            raise ValueError(
                f"reserve {reserve} needs {n_total} pages > "
                f"max_pages_per_slot {self.max_pages_per_slot}")
        n_data = max(self.pages.pages_for(s) - n_shared, 0)
        if ids is None:
            ids = self.pages.alloc(max(n_total - n_shared, n_data),
                                   tag=self._rid[slot])
        else:
            ids = np.asarray(ids, np.int32).reshape(-1)
            if ids.size < n_data:
                raise ValueError(
                    f"pre-granted {ids.size} pages cannot hold the "
                    f"{n_data}-page private prefill remainder")
        n_priv = ids.size
        if n_shared + n_priv > self.max_pages_per_slot:
            raise ValueError(
                f"{n_shared} shared + {n_priv} private pages exceed "
                f"max_pages_per_slot {self.max_pages_per_slot}")
        self._tables[slot, :n_shared] = shared_ids
        self._tables[slot, n_shared:n_shared + n_priv] = ids
        self._tables[slot, n_shared + n_priv:] = self.pages.num_pages
        req, _ = _split_len(req_cache)
        self.arena, self.lens = self._insert_jit(
            self.arena, self.lens, req, jnp.asarray(ids[:n_data]),
            jnp.asarray(slot, jnp.int32), jnp.asarray(length, jnp.int32),
            skip=n_shared)

    def assign(self, slot: int, ids: Optional[np.ndarray] = None,
               shared_ids: Optional[np.ndarray] = None,
               length: int = 0) -> None:
        """Place pre-granted pages in ``slot``'s block table WITHOUT
        scattering any prefill data — the chunked-prefill admission:
        there is no prefilled request cache yet, the coming chunk
        dispatches write K/V directly into the arena at the slot's
        cursor. ``shared_ids`` (a prefix adoption, already incref'd by
        ``reserve_batch(shared=...)``) go at the table head exactly as
        :meth:`insert` places them; ``length`` initializes the slot's
        length vector entry — the adopted-prefix extent, so the decode
        scan sharing the prefill dispatch masks the row consistently."""
        ids = (np.zeros(0, np.int32) if ids is None
               else np.asarray(ids, np.int32).reshape(-1))
        shared_ids = (np.zeros(0, np.int32) if shared_ids is None
                      else np.asarray(shared_ids, np.int32).reshape(-1))
        n_sh, n_priv = int(shared_ids.size), int(ids.size)
        if n_sh + n_priv > self.max_pages_per_slot:
            raise ValueError(
                f"{n_sh} shared + {n_priv} private pages exceed "
                f"max_pages_per_slot {self.max_pages_per_slot}")
        self._tables[slot, :n_sh] = shared_ids
        self._tables[slot, n_sh:n_sh + n_priv] = ids
        self._tables[slot, n_sh + n_priv:] = self.pages.num_pages
        self.lens = self.lens.at[int(slot)].set(int(length))

    # ----------------------------------------------------- contention signal
    def retune(self) -> Optional[Any]:
        """Adaptive wait mode: re-select the allocator's wait strategy
        from measured contention (between scheduler rounds)."""
        return self.pages.retune()

    def cache_view(self) -> PyTree:
        """Model-cache form: arena leaves + 'len' vector + block table.

        The view shares the pool's arena and length buffers, and the
        engine's round programs take it over (donation): the dispatch
        consumes it, the arena is updated in place, and the pool's own
        references are dead until :meth:`adopt` hands the round's result
        back. Nothing may read the pool's arena or lengths in between."""
        out = dict(self.arena)
        out["len"] = self.lens
        out["pages"] = jnp.asarray(self._tables)
        return out

    def adopt(self, cache: PyTree) -> None:
        """Take back the post-decode cache. The block table is host-owned
        (decode passes it through untouched), so only arena + lens are
        adopted."""
        cache = dict(cache)
        lens = cache.pop("len")
        cache.pop("pages", None)
        self.arena = cache
        self.set_lens(lens)

    def set_lens(self, lens: jax.Array) -> None:
        self.lens = lens

    # ------------------------------------------------------------ invariants
    def register_external_holder(self, fn) -> None:
        """Register a callable returning a ``{page_id: references}``
        multiset of pages owned *outside* the block tables (the prefix
        cache's retained trie). :meth:`check` folds these into its
        "every reference is accounted for" audit, so existing check()
        call sites keep passing with cache-held pages in play."""
        self._external_holders.append(fn)

    def check(self) -> None:
        """Block tables and the page pool tell one consistent story:
        every allocated page is mapped by exactly ``refcount`` slot
        rows plus registered external-holder references (the prefix
        cache) — one row per holder under prefix sharing, the
        pre-sharing "mapped by exactly one slot" when every count is
        1 and no external holder exists."""
        self.pages.check()
        mult: Dict[int, int] = {}
        for fn in getattr(self, "_external_holders", ()):
            for p, n in fn().items():
                mult[int(p)] = mult.get(int(p), 0) + int(n)
        for slot in range(self.capacity):
            row = self._tables[slot]
            real = row[row < self.pages.num_pages]
            if self._rid[slot] is None:
                assert real.size == 0, f"free slot {slot} holds pages"
            else:
                assert (row[:real.size] < self.pages.num_pages).all(), \
                    f"slot {slot} table has sentinel holes"
            for p in real.tolist():
                mult[int(p)] = mult.get(int(p), 0) + 1
        assert sorted(mult) == sorted(
            np.flatnonzero(self.pages._allocated).tolist()), \
            "block tables disagree with the allocation bitmap"
        for p, n in mult.items():
            rc = int(self.pages._refcount[p])
            assert rc == n, (
                f"page {p} mapped by {n} slot(s) but holds {rc} "
                f"reference(s) — an incref/decref escaped the protocol")
