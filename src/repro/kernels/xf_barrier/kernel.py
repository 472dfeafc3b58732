"""XF decentralized flag barrier as a Pallas TPU kernel.

The Xiao-Feng barrier (paper Section 5) on a TPU:

  * every participant *owns* one flag word — each arrive is a single-owner
    write, so the algorithm needs no atomics (TPU has none to offer);
  * the master scans the arrive array and broadcasts release flags;
  * waiting is volatile polling ("GPU sleeping") — here a bounded
    ``lax.while_loop`` re-reading the flag block each iteration;
  * the poll budget makes it a *barrier with timeout*: when it expires the
    kernel reports the exact straggler bitmap (unset flags), the property
    the host coordinator relies on and which a centralized atomic counter
    cannot provide.

TPU adaptation (DESIGN.md §2): grid steps on one TensorCore execute
sequentially, so "blocks" here are grid steps and concurrency is across
cores/chips; the flag protocol is unchanged. Epoch-numbered flags make the
barrier reusable without re-zeroing, exactly as in the paper.

Two masks separate liveness from membership: ``present`` slots write their
flag this epoch; ``required`` slots are what the master checks. A required
but non-present slot (a dead host) leaves the barrier incomplete and shows
up in the straggler bitmap.

Layout: flags live in a (1, N) int32 row (N padded to a 128-lane multiple);
per-participant writes are masked full-row vector stores — the TPU-native
form of "write your own word".
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


def _row_iota(n: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)


def xf_barrier_kernel(
    # scalar operands (SMEM)
    epoch_ref,          # (1,) int32: this barrier's epoch
    max_polls_ref,      # (1,) int32: poll budget before reporting timeout
    # array operands (VMEM)
    present_ref,        # (1, N) int32: 1 if the slot arrives this epoch
    required_ref,       # (1, N) int32: 1 if the master must see the slot
    arrive_in_ref,      # (1, N) int32: arrive flags from previous epochs
    # outputs
    arrive_ref,         # (1, N) int32
    release_ref,        # (1, N) int32
    done_ref,           # (1, 1) int32 in SMEM: 1 iff barrier completed
    straggler_ref,      # (1, N) int32: required slots that never arrived
    *,
    n_valid: int,
):
    i = pl.program_id(0)
    n = pl.num_programs(0)
    epoch = epoch_ref[0]
    iota = _row_iota(arrive_ref.shape[1])

    # Copy-through on the first step (outputs start undefined).
    @pl.when(i == 0)
    def _init():
        arrive_ref[...] = arrive_in_ref[...]
        release_ref[...] = jnp.zeros_like(release_ref)
        straggler_ref[...] = jnp.zeros_like(straggler_ref)
        done_ref[0, 0] = 0

    # ---- arrive: single-owner masked write of my flag word.
    me = (iota == i) & (present_ref[...] > 0)
    arrive_ref[...] = jnp.where(me, epoch, arrive_ref[...])

    # ---- master (last grid step on a sequential core): scan + release.
    @pl.when(i == n - 1)
    def _master():
        checked = (iota < n_valid) & (required_ref[...] > 0)

        # The "GPU sleeping" poll: the body re-reads the flag block every
        # iteration — the volatile re-read that observes remote DMA flag
        # updates; the bounded budget makes it a barrier with timeout.
        # The arrived flag is carried as int32: the chip's compiler
        # cannot carry a bool through the loop.
        max_polls = max_polls_ref[0]

        def all_arrived():
            return jnp.min(jnp.where(checked & (arrive_ref[...] < epoch),
                                     0, 1))

        def cond(state):
            polls, arrived = state
            return (arrived == 0) & (polls < max_polls)

        def body(state):
            polls, _ = state
            return polls + 1, all_arrived()

        _, arrived = jax.lax.while_loop(
            cond, body, (jnp.int32(0), all_arrived()))
        done_ref[0, 0] = arrived
        straggler_ref[...] = jnp.where(
            checked & (arrive_ref[...] < epoch), 1, 0)
        # Broadcast release flags only on success (single masked store —
        # the master's "each thread sets unique positions" step).
        release_ref[...] = jnp.where(
            checked & (arrived > 0), epoch, release_ref[...])


def xf_barrier_pallas(
    arrive: jax.Array,     # (N,) int32 flags from the previous epochs
    epoch: jax.Array,      # () int32
    present: jax.Array,    # (N,) who arrives this epoch
    required: jax.Array,   # (N,) who the master waits for
    *,
    max_polls: int = 1024,
    interpret: Optional[bool] = None,
):
    """Run one barrier epoch. Returns (arrive', release, done, stragglers)."""
    n = arrive.shape[0]
    n_pad = max(128, -(-n // 128) * 128)
    pad = n_pad - n

    def prep(x):
        return jnp.pad(x.astype(jnp.int32), (0, pad)).reshape(1, n_pad)

    kernel = functools.partial(xf_barrier_kernel, n_valid=n)
    out_shapes = (
        jax.ShapeDtypeStruct((1, n_pad), jnp.int32),  # arrive'
        jax.ShapeDtypeStruct((1, n_pad), jnp.int32),  # release
        jax.ShapeDtypeStruct((1, 1), jnp.int32),      # done
        jax.ShapeDtypeStruct((1, n_pad), jnp.int32),  # stragglers
    )
    row = pl.BlockSpec((1, n_pad), lambda i: (0, 0))
    arr, rel, done, strag = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # epoch
            pl.BlockSpec(memory_space=pltpu.SMEM),  # max_polls
            row,                                     # present
            row,                                     # required
            row,                                     # arrive_in
        ],
        out_specs=(row, row, pl.BlockSpec(memory_space=pltpu.SMEM), row),
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=kernels.resolve_interpret(interpret),
    )(
        jnp.asarray([epoch], jnp.int32),
        jnp.asarray([max_polls], jnp.int32),
        prep(present),
        prep(required),
        prep(arrive),
    )
    return arr[0, :n], rel[0, :n], done[0, 0], strag[0, :n]
