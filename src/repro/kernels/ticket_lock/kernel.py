"""Fetch-and-add (ticket) mutex as a Pallas TPU kernel.

The paper's FA mutex (Algorithm 3): lock() takes one ticket with a single
fetch-and-add, waits until the turn counter reaches it, and unlock() bumps
the turn with a plain store. It is FIFO-fair — the property this kernel
makes observable.

TPU adaptation (DESIGN.md §2): TPUs have no fetch-and-add on HBM, but a
TensorCore's grid steps execute sequentially, so a read-modify-write of an
SMEM scratch word *is* the fetch-and-add for everything scheduled on that
core — ticket issuance costs one scalar op instead of a serializing global
atomic (this is the paper's "bound the atomics" end-state, realized in
hardware scheduling). Requesters are processed in ``arrival`` order (a
permutation fed by the caller — e.g. the serving scheduler's request order),
each enters a critical section that performs an order-sensitive update
(an affine chain acc = acc*m + b, non-commutative across requesters), and
the kernel emits:

  * ``grant_order[t]``  — which requester held the lock t-th (== FIFO),
  * ``acc``             — the chain value, which is only correct if mutual
                          exclusion and FIFO order both held,
  * ``turn_trace[i]``   — the turn counter each requester observed when it
                          acquired (== its ticket; the Alg. 3 invariant).

The bounded while-loop poll on the turn word is the same "GPU sleeping"
loop as the barrier's; on one core it exits on the first check.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


def ticket_lock_kernel(
    arrival_ref,      # (1, N) int32 in VMEM: requester id per grid step
    m_ref,            # (1, N) f32: per-requester multiplier
    b_ref,            # (1, N) f32: per-requester addend
    grant_ref,        # out (1, N) int32: grant_order
    trace_ref,        # out (1, N) int32: observed turn at acquisition
    acc_ref,          # out (1, 1) f32: affine chain value
    state_ref,        # scratch SMEM (2,) int32: [ticket, turn]
):
    i = pl.program_id(0)
    n_pad = grant_ref.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_pad), 1)

    @pl.when(i == 0)
    def _init():
        state_ref[0] = 0
        state_ref[1] = 0
        grant_ref[...] = jnp.full_like(grant_ref, -1)
        trace_ref[...] = jnp.full_like(trace_ref, -1)
        acc_ref[0, 0] = 0.0

    # requester id of this grid step: an iota-masked lane reduction (a
    # dynamic lane index into a vector row does not lower on the chip)
    sel_i = iota == i
    rid = jnp.sum(jnp.where(sel_i, arrival_ref[...], 0))

    # ---- lock(): one fetch-and-add to take a ticket ...
    my_ticket = state_ref[0]
    state_ref[0] = my_ticket + 1

    # ... then sleep-wait until turn == ticket (bounded poll). The cond
    # re-reads the turn word every iteration — the volatile poll that
    # observes remote updates; on one sequential core it exits at once.
    def cond(polls):
        return (state_ref[1] != my_ticket) & (polls < 1_000_000)

    def body(polls):
        return polls + 1

    jax.lax.while_loop(cond, body, jnp.int32(0))
    observed_turn = state_ref[1]

    # ---- critical section: order-sensitive affine update + logging.
    mask_t = iota == my_ticket
    grant_ref[...] = jnp.where(mask_t, rid, grant_ref[...])
    trace_ref[...] = jnp.where(mask_t, observed_turn, trace_ref[...])
    sel = sel_i.astype(m_ref.dtype)
    m_i = jnp.sum(m_ref[...] * sel)
    b_i = jnp.sum(b_ref[...] * sel)
    acc_ref[0, 0] = acc_ref[0, 0] * m_i + b_i

    # ---- unlock(): plain store, no atomic (Alg. 3).
    state_ref[1] = my_ticket + 1


def ticket_lock_pallas(
    arrival: jax.Array,  # (N,) int32 permutation: processing order
    m: jax.Array,        # (N,) f32 per-requester multiplier
    b: jax.Array,        # (N,) f32 per-requester addend
    *,
    interpret: Optional[bool] = None,
):
    """Returns (grant_order, turn_trace, acc)."""
    n = arrival.shape[0]
    n_pad = max(128, -(-n // 128) * 128)
    pad = n_pad - n

    arrival2 = jnp.pad(arrival.astype(jnp.int32), (0, pad)).reshape(1, n_pad)
    m2 = jnp.pad(m.astype(jnp.float32), (0, pad)).reshape(1, n_pad)
    b2 = jnp.pad(b.astype(jnp.float32), (0, pad)).reshape(1, n_pad)

    row_i = pl.BlockSpec((1, n_pad), lambda i: (0, 0))
    grant, trace, acc = pl.pallas_call(
        ticket_lock_kernel,
        grid=(n,),
        in_specs=[row_i, row_i, row_i],
        out_specs=(row_i, row_i, pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=(
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        scratch_shapes=[pltpu.SMEM((2,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=kernels.resolve_interpret(interpret),
    )(arrival2, m2, b2)
    return grant[0, :n], trace[0, :n], acc[0, 0]
