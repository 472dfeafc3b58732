"""Sleeping (count/ticket/turn) semaphore as a Pallas TPU kernel.

The paper's Algorithm 5 semaphore guarantees (a) at most K holders, (b)
FIFO grant order (under-capacity arrivals enter immediately — and when the
semaphore is under capacity there are no waiters, so immediate entries are
also in arrival order), and (c) <=2 atomics per wait/post. Those semantics
make grant times *deterministic* given arrival times and hold durations:
the semaphore timeline is exactly a K-server FIFO queue — each request is
granted at

    g_i = max(arrival_i, earliest_free_slot_time)

and that is precisely the computation the serving scheduler needs to plan
admission of a request batch under a concurrency budget
(serve/scheduler.py calls this to get grant/completion estimates).

TPU adaptation (DESIGN.md §2): the count/ticket words live in SMEM scratch
and the K slot-free times in a VMEM scratch row; the sequential grid makes
every RMW exclusive on a core — ticket issuance without global atomics
(the paper's "bound the atomics" end-state, realized by hardware
scheduling). "Sleeping" becomes a deterministic handoff-time computation:
FIFO fairness means waiting never reorders, so time, not re-polling,
resolves the wait.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


_BIG = 3.4e38  # python literal: traced into the kernel as an immediate


def sleeping_semaphore_kernel(
    arrive_t_ref,   # (1, N) f32: request arrival times (sorted ascending)
    hold_ref,       # (1, N) f32: hold durations
    grant_ref,      # out (1, N) f32: grant times
    release_ref,    # out (1, N) f32: release times (grant + hold)
    waited_ref,     # out (1, N) i32: 1 if the request had to wait (ticket)
    state_ref,      # scratch SMEM (2,) int32: [count_in_flight, tickets]
    slots_ref,      # scratch VMEM (1, K_pad) f32: slot free-at times
    *,
    capacity: int,
):
    i = pl.program_id(0)
    n_pad = grant_ref.shape[1]
    k_pad = slots_ref.shape[1]
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (1, n_pad), 1)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (1, k_pad), 1)
    valid_k = iota_k < capacity

    @pl.when(i == 0)
    def _init():
        state_ref[0] = 0
        state_ref[1] = 0
        # All K slots free since t = -inf; padding slots never selectable.
        slots_ref[...] = jnp.where(valid_k, -_BIG, _BIG)
        grant_ref[...] = jnp.zeros_like(grant_ref)
        release_ref[...] = jnp.zeros_like(release_ref)
        waited_ref[...] = jnp.zeros_like(waited_ref)

    sel = iota_n == i
    arr_i = jnp.sum(jnp.where(sel, arrive_t_ref[...], 0.0))
    hold_i = jnp.sum(jnp.where(sel, hold_ref[...], 0.0))

    # ---- wait(): atomicInc(count). Under capacity -> immediate grant;
    # otherwise take a ticket (second atomic) and wait for the handoff.
    slots = jnp.where(valid_k, slots_ref[...], _BIG)
    free_t = jnp.min(slots)
    waited = free_t > arr_i  # all K slots busy at arrival
    state_ref[1] = state_ref[1] + waited.astype(jnp.int32)

    g_i = jnp.maximum(arr_i, free_t)
    r_i = g_i + hold_i

    # Occupy the earliest-free slot (FIFO handoff == ticket order because
    # arrivals are sorted and grants are monotone).
    slot_idx = jnp.argmin(slots)
    take = iota_k == slot_idx
    slots_ref[...] = jnp.where(take, r_i, slots_ref[...])

    grant_ref[...] = jnp.where(sel, g_i, grant_ref[...])
    release_ref[...] = jnp.where(sel, r_i, release_ref[...])
    waited_ref[...] = jnp.where(sel, waited.astype(jnp.int32),
                                waited_ref[...])


def sleeping_semaphore_pallas(
    arrive_t: jax.Array,  # (N,) f32, sorted ascending
    hold: jax.Array,      # (N,) f32
    capacity: int,
    *,
    interpret: Optional[bool] = None,
):
    """Returns (grant_times, release_times, waited)."""
    n = arrive_t.shape[0]
    n_pad = max(128, -(-n // 128) * 128)
    k_pad = max(128, -(-capacity // 128) * 128)
    pad = n_pad - n

    a2 = jnp.pad(arrive_t.astype(jnp.float32), (0, pad)).reshape(1, n_pad)
    h2 = jnp.pad(hold.astype(jnp.float32), (0, pad)).reshape(1, n_pad)

    row = pl.BlockSpec((1, n_pad), lambda i: (0, 0))
    kernel = functools.partial(sleeping_semaphore_kernel, capacity=capacity)
    grant, release, waited = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[row, row],
        out_specs=(row, row, row),
        out_shape=(
            jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        ),
        scratch_shapes=[
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((1, k_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=kernels.resolve_interpret(interpret),
    )(a2, h2)
    return grant[0, :n], release[0, :n], waited[0, :n]
