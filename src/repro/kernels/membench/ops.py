"""Jitted public API for the membench kernel + the TPU-row measurement."""

from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import LANE, membench_pallas
from .ref import membench_ref


@functools.partial(jax.jit, static_argnames=(
    "n_steps", "contentious", "write", "repeats", "interpret", "use_kernel"))
def membench(buf, *, n_steps: int, contentious: bool, write: bool,
             repeats: int = 16, interpret: Optional[bool] = None,
             use_kernel: bool = True):
    """Run one cell of the adapted benchmark grid; returns (buffer, sums)."""
    if use_kernel:
        return membench_pallas(buf, n_steps, contentious=contentious,
                               write=write, repeats=repeats,
                               interpret=interpret)
    return membench_ref(buf, n_steps, contentious=contentious, write=write,
                        repeats=repeats)


def make_buffer(n_steps: int, key=None) -> jax.Array:
    rows = max(8, n_steps)
    if key is None:
        return jnp.arange(rows * LANE, dtype=jnp.float32).reshape(rows, LANE) / LANE
    return jax.random.uniform(key, (rows, LANE), jnp.float32)


def time_cell(n_steps: int = 64, *, contentious: bool, write: bool,
              repeats: int = 64, interpret: Optional[bool] = None) -> float:
    """Wall-time one benchmark cell (ms per 1000 accesses per step).

    On a real TPU (interpret=False) this fills the "TPU" row of the
    machine-abstraction table; under interpret mode it times the Python
    evaluator (reported as `interpret` tier, useful only for relative
    sanity, and labeled as such in EXPERIMENTS.md).
    """
    buf = make_buffer(n_steps)
    out = membench(buf, n_steps=n_steps, contentious=contentious,
                   write=write, repeats=repeats, interpret=interpret)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = membench(buf, n_steps=n_steps, contentious=contentious,
                   write=write, repeats=repeats, interpret=interpret)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    return dt * 1e3 * (1000.0 / repeats)
