"""Backend-unified synchronization library (paper Table 4 + Section 5).

One API over every implementation substrate in the repo. The machine
abstraction picks a *(backend, algorithm, wait-strategy)* triple by
default, and every axis can be pinned:

    from repro.sync import SyncLibrary

    lib = SyncLibrary.for_host()        # probe + classify (cached per process)
    m = lib.mutex()                     # live object, best algorithm
    s = lib.semaphore(8)
    plan = lib.plan_semaphore(arrivals, holds, capacity=8)   # timeline form

    lib = SyncLibrary(machine=FERMI)            # pin a machine abstraction
    lib = SyncLibrary.host_default(backend="ref",            # pin a backend
                                   semaphore_kind="spin")    # + an algorithm

Live objects always run on the host control plane (threading); plans run
on the selected backend (Pallas interpret / hardware / pure-jnp ref /
observed host execution).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.core.abstraction import (
    BenchTimes,
    ImplChoice,
    MachineAbstraction,
    PrimitiveKind,
    WaitStrategy,
    classify,
    select_backend,
    select_impl,
)

from .backends import SyncBackend, get_backend
from .protocols import (
    BarrierPlan,
    BoundedMutexPlan,
    MutexPlan,
    SemaphorePlan,
)


class SyncTimeoutError(TimeoutError):
    """A bounded acquire exhausted its wait budget (DESIGN.md §15).

    Raised by :meth:`SyncLibrary.acquire` when the primitive's boolean
    ``timeout=`` form returns False. The primitive is *not* held: every
    host implementation leaves itself consistent on timeout (the ticket
    mutex burns its ticket, the sleeping semaphore rolls its count
    back), so the caller may retry, back off, or fail the enclosing
    operation without any cleanup."""

    def __init__(self, primitive: object, timeout_s: Optional[float],
                 what: str = ""):
        self.primitive = primitive
        self.timeout_s = timeout_s
        name = type(primitive).__name__
        super().__init__(
            f"{what or name}: not acquired within "
            f"{timeout_s if timeout_s is not None else 'inf'}s "
            f"({name})")


def _bounded_acquire(prim, timeout: Optional[float]) -> bool:
    """One bounded acquire on any live primitive: mutexes expose
    ``lock(timeout=)``, semaphores ``wait(timeout=)`` — both return
    False on expiry and leave the primitive consistent."""
    if hasattr(prim, "lock"):
        return bool(prim.lock(timeout=timeout))
    if hasattr(prim, "wait"):
        return bool(prim.wait(timeout=timeout))
    raise TypeError(f"{type(prim).__name__} has no bounded acquire form "
                    "(expected .lock or .wait)")

# A nominal host abstraction for when probing is not worth it (serving
# constructors on the hot path). Classifies as "balanced" — fa mutex,
# sleeping semaphore, xf barrier — matching the measured behavior of every
# host this repo has run on. ``for_host()`` replaces it with a real probe.
HOST_NOMINAL = MachineAbstraction(
    name="host-nominal",
    reads=BenchTimes(1.0, 0.5, 5.0, 2.5, 1.2, 0.6),
    writes=BenchTimes(1.0, 0.5, 5.0, 2.5, 1.2, 0.6),
    saturated_blocks=8,
)

# Per-process cache of the measured host abstraction (the probe runs the
# 12-benchmark grid with real threads — far too slow to repeat per call).
# Keyed by the probe parameters so a call with different measurement
# settings never silently gets an abstraction measured with other ones.
_HOST_MACHINES: dict = {}


def classified_host(refresh: bool = False, **probe_kw) -> MachineAbstraction:
    """The measured abstraction of this host, probed once per process
    (per distinct probe parameters).

    ``refresh=True`` re-runs the measurement (e.g. after CPU contention
    changes); ``probe_kw`` forwards to ``hostbench_probe.classify_host``.
    """
    key = tuple(sorted(probe_kw.items()))
    if refresh or key not in _HOST_MACHINES:
        from repro.core.hostbench_probe import classify_host
        _HOST_MACHINES[key] = classify_host(**probe_kw)
    return _HOST_MACHINES[key]


@dataclasses.dataclass
class SyncLibrary:
    """Primitive factory + planner over one machine abstraction.

    ``backend`` / ``*_kind`` / ``strategy`` pin the selection triple's
    axes; ``None`` means "let ``select_impl`` decide from the machine".
    """

    machine: MachineAbstraction
    backend: Optional[str] = None
    mutex_kind: Optional[str] = None
    semaphore_kind: Optional[str] = None
    barrier_kind: Optional[str] = None
    strategy: Optional[WaitStrategy] = None

    # ---------------------------------------------------------- constructors
    @classmethod
    def for_host(cls, refresh: bool = False, **probe_kw) -> "SyncLibrary":
        """Classify this host (measured, cached per process) and build a
        library on it. ``refresh=True`` forces a re-probe."""
        return cls(machine=classified_host(refresh=refresh, **probe_kw))

    @classmethod
    def host_default(cls, **pins) -> "SyncLibrary":
        """Probe-free library on the nominal host abstraction — the
        cheap constructor for serving hot paths."""
        return cls(machine=HOST_NOMINAL, **pins)

    # ------------------------------------------------------------- selection
    def choice(self, primitive: PrimitiveKind, **kw) -> ImplChoice:
        return select_impl(self.machine, primitive, backend=self.backend,
                           **kw)

    def machine_class(self) -> str:
        return classify(self.machine)

    def backend_name(self) -> str:
        return self.backend or select_backend(self.machine)

    def _backend(self, override: Optional[str] = None) -> SyncBackend:
        return get_backend(override or self.backend_name())

    def planning_backend_name(self) -> str:
        """Backend for hot-loop planning: the pinned/selected backend if
        it plans cheaply, else the kernel backend (compiled on a TPU,
        interpreted elsewhere)."""
        name = self.backend_name()
        return name if get_backend(name).fast_plans else "kernel"

    # ------------------------------------------------------------- live form
    def mutex(self, kind: Optional[str] = None, *,
              expected_contention: float = 1.0,
              strategy: Optional[WaitStrategy] = None):
        """Live mutex. ``expected_contention`` (fraction of participants
        expected to contend at once) feeds the paper's Section-6 wait-
        strategy relaxation — hot allocators pass their own estimate.

        ``kind="adaptive"`` returns a contention-adaptive FIFO ticket
        mutex (``hostsync.AdaptiveMutex``): it starts on the strategy
        selected for ``expected_contention`` and re-selects
        spin / spin-backoff / sleep from its own measured contention
        window whenever the owner calls ``retune()`` — between scheduler
        rounds, never mid-critical-section. ``strategy`` pins the wait
        strategy for this one mutex (the sweep benchmarks use it to pin
        each arm); the library-level ``self.strategy`` pin still wins.
        """
        c = self.choice(PrimitiveKind.MUTEX,
                        expected_contention=expected_contention)
        kind = kind or self.mutex_kind or c.algorithm
        strat = self.strategy or strategy or c.strategy
        if kind == "adaptive":
            from repro.core.hostsync import AdaptiveMutex
            inner = self._backend().mutex("ticket", strat)
            return AdaptiveMutex(inner, self.machine)
        return self._backend().mutex(kind, strat)

    def semaphore(self, initial: int, kind: Optional[str] = None):
        c = self.choice(PrimitiveKind.SEMAPHORE, semaphore_initial=initial)
        kind = kind or self.semaphore_kind or c.algorithm
        return self._backend().semaphore(initial, kind,
                                         self.strategy or c.strategy)

    def barrier(self, parties: int, kind: Optional[str] = None):
        c = self.choice(PrimitiveKind.BARRIER)
        kind = kind or self.barrier_kind or c.algorithm
        return self._backend().barrier(parties, kind,
                                       self.strategy or c.strategy)

    # --------------------------------------------------------- bounded waits
    @staticmethod
    def acquire(prim, timeout: Optional[float] = None,
                what: str = "") -> None:
        """Acquire a live mutex/semaphore, raising
        :class:`SyncTimeoutError` if ``timeout`` (seconds) expires — the
        exception-typed form of the primitives' boolean ``timeout=``
        protocol. ``timeout=None`` waits unboundedly (never raises)."""
        if not _bounded_acquire(prim, timeout):
            raise SyncTimeoutError(prim, timeout, what)

    @staticmethod
    def try_acquire(prim) -> bool:
        """Non-blocking-intent acquire: a zero-budget bounded acquire.
        True iff the primitive was taken immediately. Note the FIFO
        ticket mutex's timeout discipline still *burns a ticket* on
        failure (it briefly waits for its turn so later tickets never
        deadlock) — bounded, but up to one holder's critical section,
        not strictly O(1)."""
        return _bounded_acquire(prim, 0.0)

    # ------------------------------------------------------------- plan form
    def plan_semaphore(self, arrivals, holds, capacity: int, *,
                       backend: Optional[str] = None,
                       window: Optional[int] = None) -> SemaphorePlan:
        """Deterministic Algorithm-5 timeline for a FIFO request trace.

        Arrivals need not be sorted; the plan is returned in the caller's
        order (sort + inverse-permute happen here, uniformly for every
        backend)."""
        arrivals = np.asarray(arrivals, np.float32)
        holds = np.asarray(holds, np.float32)
        perm = np.argsort(arrivals, kind="stable")
        bk = self._backend(backend)
        g, r, w, order = bk.plan_semaphore(
            arrivals[perm], holds[perm], capacity, window=window)
        inv = np.argsort(perm, kind="stable")
        return SemaphorePlan(
            arrivals=arrivals,
            grant=np.asarray(g)[inv],
            release=np.asarray(r)[inv],
            waited=np.asarray(w)[inv],
            capacity=capacity,
            backend=bk.name,
            order=None if order is None else perm[np.asarray(order)],
        )

    def plan_mutex(self, arrival, m=None, b=None, *,
                   backend: Optional[str] = None,
                   window: Optional[int] = None) -> MutexPlan:
        """FIFO ticket-mutex timeline for requesters in ``arrival`` order
        (a permutation of 0..N-1). ``m``/``b`` parameterize the
        order-sensitive critical-section chain (default: identity)."""
        arrival = np.asarray(arrival, np.int64)
        n = arrival.shape[0]
        m = np.ones(n, np.float32) if m is None else np.asarray(m, np.float32)
        b = np.zeros(n, np.float32) if b is None else np.asarray(b, np.float32)
        bk = self._backend(backend)
        g, t, acc = bk.plan_mutex(arrival, m, b, window=window)
        return MutexPlan(arrival=arrival, grant_order=np.asarray(g),
                         turn_trace=np.asarray(t), acc=float(acc),
                         backend=bk.name)

    def plan_mutex_bounded(self, arrivals, holds, timeouts, *,
                           backend: Optional[str] = None,
                           window: Optional[int] = None
                           ) -> BoundedMutexPlan:
        """Bounded-wait FIFO mutex timeline: the plan form of
        ``lock(timeout=)`` (DESIGN.md §15).

        Each requester carries a wait budget in ``timeouts`` (np.inf =
        unbounded). A requester whose turn would arrive after its budget
        burns its ticket — it is never granted and holds for zero time,
        exactly the live ``TicketMutex`` discipline. Burned tickets
        shorten every later wait, so the timeline is computed as a fixed
        point: replan the capacity-1 semaphore timeline (a mutex *is*
        the capacity-1 case, and the semaphore plan is the one form
        every backend reports per-requester grant times for) with
        burned holds zeroed until the burned set stabilizes. Decisions
        fix in FIFO-prefix order, so at most N+1 replans are needed —
        in practice 2–3.

        The ``granted`` mask is the cross-backend equivalence object:
        host (observed execution), kernel, and ref must agree with the
        step-exact numpy oracle
        (``kernels.ticket_lock.ops.ticket_lock_bounded_oracle``)."""
        arrivals = np.asarray(arrivals, np.float32)
        holds = np.asarray(holds, np.float32)
        timeouts = np.asarray(timeouts, np.float32)
        n = arrivals.shape[0]
        if holds.shape != arrivals.shape or timeouts.shape != arrivals.shape:
            raise ValueError("arrivals/holds/timeouts must align")
        granted = np.ones(n, bool)
        live = holds.copy()
        plan = None
        iterations = 0
        for _ in range(n + 2):
            plan = self.plan_semaphore(arrivals, live, 1, backend=backend,
                                       window=window)
            iterations += 1
            # equality is "granted": the live mutex times out only when
            # the deadline strictly passes (small tolerance for the
            # float32 event clocks)
            now = (plan.grant - arrivals) <= timeouts + 1e-4
            if np.array_equal(now, granted):
                break
            granted = now
            live = np.where(granted, holds, 0.0).astype(np.float32)
        else:
            raise RuntimeError("bounded mutex plan did not stabilize")
        return BoundedMutexPlan(
            arrivals=arrivals, holds=holds, timeouts=timeouts,
            grant=np.asarray(plan.grant),
            release=np.asarray(plan.release),
            granted=granted, backend=plan.backend,
            iterations=iterations)

    def plan_barrier(self, present, required=None, *, epoch: int = 1,
                     flags=None, max_polls: int = 1024,
                     backend: Optional[str] = None,
                     window: Optional[int] = None) -> BarrierPlan:
        """One XF-barrier epoch: ``present`` slots arrive, the master
        checks ``required`` slots (default: all)."""
        present = np.asarray(present, np.int64)
        n = present.shape[0]
        required = (np.ones(n, np.int64) if required is None
                    else np.asarray(required, np.int64))
        flags = (np.zeros(n, np.int64) if flags is None
                 else np.asarray(flags, np.int64))
        bk = self._backend(backend)
        a, rel, done, strag = bk.plan_barrier(
            flags, epoch, present, required, max_polls=max_polls,
            window=window)
        return BarrierPlan(epoch=int(epoch), arrive=np.asarray(a),
                           release=np.asarray(rel), done=int(done),
                           stragglers=np.asarray(strag), required=required,
                           backend=bk.name)
