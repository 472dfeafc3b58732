"""Jitted public API for the ticket-lock kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import numpy as np

from repro.sync.window import WindowedPlanner

from .kernel import ticket_lock_pallas
from .ref import ticket_lock_ref


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def ticket_lock_run(arrival, m, b, *, interpret: Optional[bool] = None,
                    use_kernel: bool = True):
    """Process N lock requests in ``arrival`` order under a FIFO ticket
    mutex; returns (grant_order, turn_trace, acc)."""
    if use_kernel:
        return ticket_lock_pallas(arrival, m, b, interpret=interpret)
    return ticket_lock_ref(arrival, m, b)


def _pad_ticket(arrays, n: int, window: int):
    """Pad with identity requesters arriving last: ids n..window-1 take
    the trailing tickets (real grants stay in the first n positions) and
    m=1, b=0 leaves the affine chain untouched."""
    arrival, m, b = arrays
    pad = window - n
    return (np.concatenate([arrival, np.arange(n, window, dtype=np.int32)]),
            np.concatenate([m, np.ones(pad, np.float32)]),
            np.concatenate([b, np.zeros(pad, np.float32)]))


_ticket_window = WindowedPlanner(
    plan=ticket_lock_run, pad=_pad_ticket,
    base_window=32, name="ticket_lock_window")


def ticket_lock_window(arrival, m=None, b=None, *, window: int = 32,
                       interpret: Optional[bool] = None,
                       use_kernel: bool = True):
    """Fixed-shape ticket-lock planning (power-of-2 bucketed windows —
    see ``repro.sync.window.WindowedPlanner``), so schedulers replanning
    varying request counts reuse one compiled kernel per bucket.

    Returns numpy ``(grant_order, turn_trace, acc)`` of the original
    length.
    """
    arrival = np.asarray(arrival, np.int32)
    n = arrival.shape[0]
    m = (np.ones(n, np.float32) if m is None
         else np.asarray(m, np.float32))
    b = (np.zeros(n, np.float32) if b is None
         else np.asarray(b, np.float32))
    return _ticket_window(arrival, m, b, window=window,
                          interpret=interpret, use_kernel=use_kernel)


def ticket_lock_bounded_oracle(arrivals, holds, timeouts):
    """Step-exact oracle for the *bounded-wait* FIFO ticket mutex: the
    ground truth ``SyncLibrary.plan_mutex_bounded`` must reach on every
    backend (DESIGN.md §15).

    Tickets issue in stable arrival order. Walking tickets in order with
    a running lock-free time: requester ``i``'s turn arrives at
    ``max(arrival_i, t_free)``; if the wait exceeds ``timeout_i`` the
    ticket *burns* — never granted, zero hold, the turn passes
    immediately (the live ``TicketMutex`` timeout discipline) — else it
    holds for ``hold_i``. One forward pass is exact because a ticket's
    fate depends only on earlier tickets' fates.

    Returns ``(granted, grant, release)``: bool mask + turn/release
    times, caller order.
    """
    arrivals = np.asarray(arrivals, np.float64)
    holds = np.asarray(holds, np.float64)
    timeouts = np.asarray(timeouts, np.float64)
    n = arrivals.shape[0]
    granted = np.zeros(n, bool)
    grant = np.zeros(n, np.float64)
    release = np.zeros(n, np.float64)
    t_free = -np.inf
    for i in np.argsort(arrivals, kind="stable"):
        g = max(float(arrivals[i]), t_free)
        grant[i] = g
        if g - arrivals[i] > timeouts[i]:
            release[i] = g                    # burned: pass the turn on
            t_free = g
        else:
            granted[i] = True
            release[i] = g + holds[i]
            t_free = release[i]
    return granted, grant, release


def ticket_lock_batch_window(arrival, counts, *, window: int = 32,
                             interpret: Optional[bool] = None,
                             use_kernel: bool = True):
    """Plan one *batched-grant* allocator round under the FIFO ticket
    lock: requester ``i``'s single critical section grants
    ``counts[i]`` pages (the ``PagePool.alloc_batch`` discipline), so
    the round costs one fetch-and-add per requester instead of one per
    page.

    Runs the same Algorithm-3 kernel as :func:`ticket_lock_window` with
    the page counts riding the critical-section chain (``m=1``,
    ``b=counts`` — the affine accumulator becomes the running page
    total), on the same power-of-2 bucketed windows. Returns numpy

      * ``grant_order`` — requester ids in lock-grant (FIFO ticket)
        order: identical to the order a per-page loop would grant, the
        equivalence the batched allocator relies on;
      * ``pages_start`` — exclusive running page total when each
        requester (``counts`` is positional, like ``m``/``b``: entry
        ``j`` belongs to the ``j``-th ticket, which is also the ``j``-th
        grant) enters its critical section: the offset of its first
        granted page in the round's FIFO page stream;
      * ``total_pages`` — pages granted by the whole round;
      * ``atomics`` — ``(batched, per_page)`` synchronizing-access
        counts for the round: ``n`` one-FA acquires vs the
        ``total_pages`` a page-at-a-time loop would have issued — the
        paper-currency saving the serving benchmarks report.
    """
    arrival = np.asarray(arrival, np.int32)
    counts = np.asarray(counts, np.int64)
    if counts.shape != arrival.shape:
        raise ValueError("counts must have one entry per requester")
    if np.any(counts < 0):
        raise ValueError("negative page count")
    n = arrival.shape[0]
    grant_order, _, total = _ticket_window(
        arrival, np.ones(n, np.float32), counts.astype(np.float32),
        window=window, interpret=interpret, use_kernel=use_kernel)
    grant_order = np.asarray(grant_order, np.int64)
    pages_start = np.concatenate(
        [[0], np.cumsum(counts)[:-1]]) if n else np.zeros(0, np.int64)
    total_pages = int(round(float(total)))
    return grant_order, pages_start, total_pages, (n, total_pages)
