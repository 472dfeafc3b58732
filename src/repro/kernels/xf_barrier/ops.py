"""Jitted public API for the XF barrier kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.sync.window import WindowedPlanner

from .kernel import xf_barrier_pallas
from .ref import xf_barrier_ref


@functools.partial(jax.jit, static_argnames=("max_polls", "interpret", "use_kernel"))
def xf_barrier(
    arrive: jax.Array,
    epoch: jax.Array,
    present: jax.Array,
    required: jax.Array,
    *,
    max_polls: int = 1024,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
):
    """One XF barrier epoch over flag words.

    Returns ``(arrive', release, done, stragglers)``. ``use_kernel=False``
    routes through the pure-jnp reference (used on back ends without
    Pallas TPU support).
    """
    if use_kernel:
        return xf_barrier_pallas(
            arrive, epoch, present, required,
            max_polls=max_polls, interpret=interpret)
    return xf_barrier_ref(arrive, epoch, present, required,
                          max_polls=max_polls)


def fresh_flags(n: int) -> jax.Array:
    return jnp.zeros((n,), jnp.int32)


def _pad_barrier(arrays, n: int, window: int):
    """Pad with absent, non-required slots: they never arrive and the
    master never checks them, so done/stragglers are unchanged."""
    arrive, present, required = arrays
    pad = window - n
    z = np.zeros(pad, np.int32)
    return (np.concatenate([arrive, z]),
            np.concatenate([present, z]),
            np.concatenate([required, z]))


def _barrier_plan(arrive, present, required, *, epoch, max_polls,
                  interpret, use_kernel):
    return xf_barrier(jnp.asarray(arrive), jnp.int32(epoch),
                      jnp.asarray(present), jnp.asarray(required),
                      max_polls=max_polls, interpret=interpret,
                      use_kernel=use_kernel)


_barrier_window = WindowedPlanner(
    plan=_barrier_plan, pad=_pad_barrier,
    base_window=32, name="xf_barrier_window")


def xf_barrier_window(arrive, epoch, present, required, *,
                      max_polls: int = 1024, window: int = 32,
                      interpret: Optional[bool] = None,
                      use_kernel: bool = True):
    """Fixed-shape barrier epoch (power-of-2 bucketed windows — see
    ``repro.sync.window.WindowedPlanner``), so membership churn across
    epochs reuses one compiled kernel per world-size bucket.

    Returns numpy ``(arrive', release, done, stragglers)`` of the
    original length.
    """
    arrive = np.asarray(arrive, np.int32)
    present = np.asarray(present, np.int32)
    required = np.asarray(required, np.int32)
    return _barrier_window(arrive, present, required, window=window,
                           epoch=int(epoch), max_polls=max_polls,
                           interpret=interpret, use_kernel=use_kernel)
