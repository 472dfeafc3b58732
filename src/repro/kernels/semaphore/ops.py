"""Jitted public API for the sleeping-semaphore kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import numpy as np

from repro.sync.window import WindowedPlanner

from .kernel import sleeping_semaphore_pallas
from .ref import sleeping_semaphore_ref


@functools.partial(jax.jit,
                   static_argnames=("capacity", "interpret", "use_kernel"))
def semaphore_admission(arrive_t, hold, *, capacity: int,
                        interpret: Optional[bool] = None,
                        use_kernel: bool = True):
    """Plan admission of N FIFO requests under a concurrency budget K.

    Returns (grant_times, release_times, waited) — the deterministic
    timeline of the paper's Algorithm 5 sleeping semaphore. Used by the
    serving scheduler for continuous-batching admission planning.
    """
    if use_kernel:
        return sleeping_semaphore_pallas(
            arrive_t, hold, capacity, interpret=interpret)
    return sleeping_semaphore_ref(arrive_t, hold, capacity)


def _pad_admission(arrays, n: int, window: int):
    """Pad with far-future zero-hold arrivals: they keep the arrival sort
    ascending and can never steal a slot from a real request before it is
    granted."""
    arrive_t, hold = arrays
    horizon = (float(arrive_t.max()) if n else 0.0) + 1e6
    pad_arr = horizon + np.arange(window - n, dtype=np.float32)
    return (np.concatenate([arrive_t, pad_arr]),
            np.concatenate([hold, np.zeros(window - n, np.float32)]))


_admission_window = WindowedPlanner(
    plan=semaphore_admission, pad=_pad_admission,
    base_window=32, name="semaphore_admission_window")


def semaphore_admission_window(arrive_t, hold, *, capacity: int,
                               window: int = 32,
                               interpret: Optional[bool] = None,
                               use_kernel: bool = True):
    """Fixed-shape admission planning for the serving hot loop.

    ``semaphore_admission`` compiles per input length; the slot engine
    replans admission every scheduler round with a varying number of
    in-flight + queued requests, which would retrace the kernel each
    round. This wrapper (a ``repro.sync.window.WindowedPlanner``) pads
    the trace to a fixed ``window`` and slices the padding back off, so
    one compiled kernel serves every round. Bursts longer than the window
    bucket up to the next power-of-2 multiple — a bounded set of traced
    shapes — with a one-time warning instead of failing the hot loop.

    Returns numpy ``(grant, release, waited)`` of the original length.
    """
    arrive_t = np.asarray(arrive_t, np.float32)
    hold = np.asarray(hold, np.float32)
    return _admission_window(arrive_t, hold, window=window,
                             capacity=capacity, interpret=interpret,
                             use_kernel=use_kernel)
