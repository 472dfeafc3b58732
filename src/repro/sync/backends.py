"""Backend registry: one implementation substrate per machine class.

A *backend* is where a primitive actually runs:

  ``host``    — the real threading implementations in ``core/hostsync``.
                Live objects are native; plans are produced by executing
                the live primitives under a driver-owned event clock and
                *observing* the grant order (this is what the
                cross-backend equivalence tests pin the kernels against).
  ``kernel``  — the Pallas kernels, compiled on a TPU and run under the
                interpreter elsewhere (``repro.kernels.default_interpret``
                decides; the CPU/CI tier is the interpreter).
  ``ref``     — the pure-jnp oracles (``kernels/*/ref.py``).

Live objects always execute on the host control plane — a Pallas kernel
is a planner, not a resident lock — so the kernel-family backends inherit
the host constructors. Plans route to the backend's substrate.

Custom backends (e.g. a future multi-replica coordinator) register via
``register_backend``; ``select_impl`` names backends in its selection
triple, so a machine abstraction can steer traffic to them.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from repro.core import hostsync
from repro.core.abstraction import WaitStrategy

# Host algorithm tables. The host can truly block, so "auto" on a host
# machine may pick the futex, which the paper identifies as CPU-only (no
# blocking on the GPU).
HOST_MUTEXES = {
    "spin": lambda strat: hostsync.SpinMutex(strategy=WaitStrategy.SPIN),
    "spin_backoff": lambda strat: hostsync.SpinMutex(
        strategy=WaitStrategy.SPIN_BACKOFF),
    "fa": lambda strat: hostsync.TicketMutex(strategy=strat),
    "ticket": lambda strat: hostsync.TicketMutex(strategy=strat),
    "futex": lambda strat: hostsync.FutexMutex(),
}
HOST_SEMAPHORES = {
    "spin": lambda n, strat: hostsync.SpinSemaphore(
        n, strategy=WaitStrategy.SPIN),
    "spin_backoff": lambda n, strat: hostsync.SpinSemaphore(
        n, strategy=WaitStrategy.SPIN_BACKOFF),
    "sleeping": lambda n, strat: hostsync.SleepingSemaphore(n, strategy=strat),
}
HOST_BARRIERS = {
    "xf": lambda p, strat: hostsync.XFBarrier(p, strategy=strat),
    "atomic": lambda p, strat: hostsync.CentralizedBarrier(p, strategy=strat),
    "centralized": lambda p, strat: hostsync.CentralizedBarrier(
        p, strategy=strat),
}


class SyncBackend:
    """Base backend: live constructors delegate to the host substrate."""

    name = "base"
    #: cheap, deterministic plans suitable for a scheduler hot loop
    fast_plans = False
    #: where this backend's plans execute
    tier = "host"

    # ------------------------------------------------------------- live form
    def mutex(self, algorithm: str, strategy: WaitStrategy):
        return HOST_MUTEXES[algorithm](strategy)

    def semaphore(self, initial: int, algorithm: str,
                  strategy: WaitStrategy):
        return HOST_SEMAPHORES[algorithm](initial, strategy)

    def barrier(self, parties: int, algorithm: str,
                strategy: WaitStrategy):
        return HOST_BARRIERS[algorithm](parties, strategy)

    # ------------------------------------------------------------- plan form
    def plan_semaphore(self, arrivals, holds, capacity: int, *,
                       window: Optional[int] = None
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                  Optional[np.ndarray]]:
        """(grant, release, waited, observed_order_or_None) for a trace
        sorted ascending by arrival."""
        raise NotImplementedError

    def plan_mutex(self, arrival, m, b, *, window: Optional[int] = None
                   ) -> Tuple[np.ndarray, np.ndarray, float]:
        """(grant_order, turn_trace, acc) for requesters in ``arrival``
        order (a permutation of 0..N-1)."""
        raise NotImplementedError

    def plan_barrier(self, arrive, epoch: int, present, required, *,
                     max_polls: int = 1024, window: Optional[int] = None
                     ) -> Tuple[np.ndarray, np.ndarray, int, np.ndarray]:
        """(arrive', release, done, stragglers) for one barrier epoch."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# The Pallas-kernel backend and the pure-jnp reference.
# Kernel modules are imported lazily inside the methods so that importing
# ``repro.sync`` never pulls in jax.pallas (and so the kernel ops modules
# can themselves import ``repro.sync.window`` without a cycle).
# ---------------------------------------------------------------------------

class PallasBackend(SyncBackend):
    """Plans via the Pallas kernels, whose tier the platform decides;
    ``use_kernel=False`` plans with the pure-jnp oracles."""

    fast_plans = True

    def __init__(self, name: str, use_kernel: bool = True):
        self.name = name
        self.use_kernel = use_kernel

    @property
    def tier(self) -> str:
        """Where plans run: ``compiled`` kernel, ``interpret`` kernel,
        or the ``ref`` oracles."""
        if not self.use_kernel:
            return "ref"
        from repro import kernels
        return "interpret" if kernels.resolve_interpret(None) else "compiled"

    def plan_semaphore(self, arrivals, holds, capacity, *, window=None):
        from repro.kernels.semaphore.ops import semaphore_admission_window
        g, r, w = semaphore_admission_window(
            arrivals, holds, capacity=capacity,
            window=window if window else 32,
            use_kernel=self.use_kernel)
        return np.asarray(g), np.asarray(r), np.asarray(w), None

    def plan_mutex(self, arrival, m, b, *, window=None):
        from repro.kernels.ticket_lock.ops import ticket_lock_window
        g, t, acc = ticket_lock_window(
            arrival, m, b, window=window if window else 32,
            use_kernel=self.use_kernel)
        return np.asarray(g), np.asarray(t), float(acc)

    def plan_barrier(self, arrive, epoch, present, required, *,
                     max_polls=1024, window=None):
        from repro.kernels.xf_barrier.ops import xf_barrier_window
        a, rel, done, strag = xf_barrier_window(
            arrive, epoch, present, required, max_polls=max_polls,
            window=window if window else 32,
            use_kernel=self.use_kernel)
        return (np.asarray(a), np.asarray(rel), int(done),
                np.asarray(strag))


# ---------------------------------------------------------------------------
# Host backend: live primitives are native; plans execute them for real.
# ---------------------------------------------------------------------------

_POLL_S = 50e-6


def _wait_until(pred, what: str, timeout_s: float = 20.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not pred():
        if time.monotonic() > deadline:
            raise RuntimeError(f"host plan stalled waiting for {what}")
        time.sleep(_POLL_S)


class HostBackend(SyncBackend):
    """Real threading primitives; plans are observed executions.

    The driver owns a virtual event clock (arrival and completion events
    processed in time order) while the *ordering* decisions — who enters,
    who is handed off next — are made by the real primitive under test.
    This is deliberately not fast: it exists to pin the kernel planners'
    semantics to the genuine Algorithm-3/5/XF implementations, and it is
    what the cross-backend equivalence property tests run.
    """

    name = "host"
    fast_plans = False

    def plan_semaphore(self, arrivals, holds, capacity, *, window=None):
        del window
        arrivals = np.asarray(arrivals, np.float32)
        holds = np.asarray(holds, np.float32)
        n = int(arrivals.shape[0])
        if n == 0:
            z = np.zeros(0, np.float32)
            return z, z, np.zeros(0, np.int32), np.zeros(0, np.int64)
        if np.any(np.diff(arrivals) < 0):
            raise ValueError("arrivals must be sorted ascending")

        sem = hostsync.SleepingSemaphore(capacity)
        lock = threading.Lock()
        order = []
        release_ev = [threading.Event() for _ in range(n)]

        def worker(i):
            sem.wait()
            with lock:
                order.append(i)
            release_ev[i].wait(timeout=20.0)
            sem.post()

        def grants():
            with lock:
                return len(order)

        grant = np.zeros(n, np.float32)
        waited = np.zeros(n, np.int32)
        threads = []
        active: Dict[int, np.float32] = {}  # i -> release time
        queue = []                          # ticketed waiters, FIFO
        spawned = 0
        n_granted = 0  # driver-side count; grants only happen on our events
        n_tickets = 0  # tickets ever issued (over-capacity arrivals)
        inf = float("inf")
        while spawned < n or active:
            next_arr = float(arrivals[spawned]) if spawned < n else inf
            if active:
                rel_i = min(active, key=lambda j: (float(active[j]), j))
                next_rel = float(active[rel_i])
            else:
                next_rel = inf
            if next_rel <= next_arr:
                # ---- completion event: post() hands off to the oldest
                # waiter (Algorithm 5); a slot freeing exactly at an
                # arrival is processed first so the arrival sees it free.
                now = active.pop(rel_i)
                release_ev[rel_i].set()
                if queue:
                    j = queue.pop(0)
                    n_granted += 1
                    _wait_until(lambda: grants() >= n_granted,
                                "FIFO handoff")
                    grant[j] = now
                    active[j] = now + holds[j]
                else:
                    expect = len(active) + len(queue)
                    _wait_until(lambda: sem._count.load() == expect,
                                "post to drain")
            else:
                # ---- arrival event: spawn the requester; whether it
                # enters or tickets is the real semaphore's decision.
                i = spawned
                spawned += 1
                t = threading.Thread(target=worker, args=(i,))
                t.start()
                threads.append(t)
                expect = len(active) + len(queue) + 1
                _wait_until(lambda: sem._count.load() == expect,
                            "wait() entry")
                if len(active) < capacity:
                    n_granted += 1
                    _wait_until(lambda: grants() >= n_granted,
                                "immediate entry")
                    grant[i] = next_arr
                    active[i] = np.float32(next_arr) + holds[i]
                else:
                    # wait() is count.fetch_add *then* ticket.fetch_add;
                    # gate on the ticket too, or a preempted requester
                    # could let the next arrival steal its FIFO slot
                    n_tickets += 1
                    _wait_until(lambda: sem._ticket.load() == n_tickets,
                                "ticket issuance")
                    waited[i] = 1
                    queue.append(i)
        for t in threads:
            t.join()
        return grant, grant + holds, waited, np.asarray(order, np.int64)

    def plan_mutex(self, arrival, m=None, b=None, *, window=None):
        del window
        arrival = np.asarray(arrival, np.int64)
        n = int(arrival.shape[0])
        if n == 0:
            z = np.zeros(0, np.int64)
            return z, z, 0.0
        m = np.ones(n, np.float32) if m is None else np.asarray(m, np.float32)
        b = np.zeros(n, np.float32) if b is None else np.asarray(b, np.float32)
        mtx = hostsync.TicketMutex(strategy=WaitStrategy.SLEEP)
        order, turns = [], []
        acc = [np.float32(0.0)]
        everyone_queued = threading.Event()

        def worker(j):
            mtx.lock()
            if not order:
                # first holder stalls inside the critical section until
                # every later requester holds a ticket — real contention,
                # so the FIFO drain below is a meaningful observation
                everyone_queued.wait(timeout=20.0)
            order.append(int(arrival[j]))
            turns.append(int(mtx._turn))
            acc[0] = acc[0] * m[j] + b[j]
            mtx.unlock()

        threads = []
        for j in range(n):
            t = threading.Thread(target=worker, args=(j,))
            t.start()
            threads.append(t)
            # ticket issuance must follow arrival order: each requester
            # holds its ticket before the next one is spawned
            _wait_until(lambda: mtx._ticket.load() == j + 1,
                        "ticket issuance")
        everyone_queued.set()
        for t in threads:
            t.join()
        return (np.asarray(order, np.int64), np.asarray(turns, np.int64),
                float(acc[0]))

    def plan_barrier(self, arrive, epoch, present, required, *,
                     max_polls=1024, window=None, timeout_s=0.5):
        del max_polls, window
        arrive = np.asarray(arrive, np.int64)
        present = np.asarray(present, np.int64) > 0
        required = np.asarray(required, np.int64) > 0
        n = int(arrive.shape[0])
        epoch = int(epoch)
        if n == 0:
            # vacuous completion, matching the kernel/ref semantics
            z = np.zeros(0, np.int64)
            return z, z, 1, z

        bar = hostsync.XFBarrier(n, strategy=WaitStrategy.SPIN_BACKOFF,
                                 required=required.tolist())
        bar._arrive = [int(a) for a in arrive]
        bar._epochs = [epoch - 1] * n

        results = {}

        def worker(rank):
            results[rank] = bar.arrive_and_wait(rank, timeout=timeout_s)

        threads = [threading.Thread(target=worker, args=(r,))
                   for r in range(n) if present[r]]
        for t in threads:
            t.start()
        master_present = bool(n) and bool(present[0])
        if not master_present:
            # rank 0 is the XF master; when it is absent the driver plays
            # master (scan required flags, broadcast on success) so the
            # host run keeps the kernel's semantics (the kernel's master
            # is a grid step that always executes).
            deadline = time.monotonic() + timeout_s
            ok = False
            while time.monotonic() < deadline:
                if all(bar._arrive[k] >= epoch
                       for k in range(n) if required[k]):
                    ok = True
                    break
                time.sleep(_POLL_S)
            if ok:
                for k in range(n):
                    bar._release[k] = epoch
            done = int(ok)
        else:
            for t in threads:
                t.join()
            done = int(results.get(0, False))
        for t in threads:
            t.join()

        new_arrive = np.asarray(bar._arrive, np.int64)
        stragglers = np.where(required & (new_arrive < epoch), 1, 0)
        release = np.asarray(bar._release, np.int64)
        return new_arrive, release, done, stragglers.astype(np.int64)


# ---------------------------------------------------------------------------
# Registry.
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, SyncBackend] = {}


def register_backend(name: str, backend: SyncBackend) -> SyncBackend:
    """Register (or replace) a backend under ``name``."""
    backend.name = name
    _REGISTRY[name] = backend
    return backend


def get_backend(name: str) -> SyncBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown sync backend {name!r}; available: "
            f"{', '.join(available_backends())}") from None


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register_backend("host", HostBackend())
register_backend("kernel", PallasBackend("kernel"))
register_backend("ref", PallasBackend("ref", use_kernel=False))
