"""The paper's memory benchmarks (Section 3) as a Pallas TPU kernel.

The original twelve benchmarks sweep {atomic, volatile} x {contentious,
noncontentious} x {read, write}. On a TPU there is no atomic axis — the
adapted sweep is {contentious, noncontentious} x {read, write} over HBM
words accessed from a kernel, where:

  * contentious  — every grid step hammers the *same* word-row of the
    shared buffer (one memory line's worth of traffic);
  * noncontentious — grid step i hammers its *own* row, rows padded to
    distinct 512-byte HBM tiles (the paper's 256-byte separation, scaled
    to TPU line size).

On real TPU hardware the wrapper times these to fill the "TPU row" of the
machine-abstraction table; under interpret mode the kernel's *semantics*
are validated against ref.py (final buffer contents + checksums must agree
exactly), which is what CI on this container runs. ``repeats`` loads/stores
per step run in a ``fori_loop``, mirroring the paper's 1000-access loop.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


LANE = 128  # f32 lane width; one (8, 128) tile = 4 KiB = one HBM tile


def membench_kernel(
    buf_in_ref,     # (R, LANE) f32: the shared buffer (aliased to output)
    buf_ref,        # out (R, LANE) f32
    sums_ref,       # out (1, N_pad) f32: per-step read checksums
    *,
    contentious: bool,
    write: bool,
    repeats: int,
):
    i = pl.program_id(0)
    n_pad = sums_ref.shape[1]
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (1, n_pad), 1)
    rows = buf_ref.shape[0]
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)

    @pl.when(i == 0)
    def _init():
        buf_ref[...] = buf_in_ref[...]
        sums_ref[...] = jnp.zeros_like(sums_ref)

    row = 0 if contentious else None  # noncontentious: my own row
    row_idx = jnp.int32(0) if contentious else i
    mask = iota_r == row_idx

    if write:
        def body(it, _):
            # store: buf[row] = it + step-id (last write wins — visible in
            # the final buffer, which the oracle reproduces exactly).
            val = (it + i + 1).astype(jnp.float32)
            buf_ref[...] = jnp.where(mask, val, buf_ref[...])
            return _
        jax.lax.fori_loop(0, repeats, body, 0)
        checksum = jnp.sum(jnp.where(mask, buf_ref[...], 0.0))
    else:
        def body(it, acc):
            # load: accumulate the row (the re-read each iteration is the
            # volatile poll; on hardware this is the timed HBM round trip).
            return acc + jnp.sum(jnp.where(mask, buf_ref[...], 0.0))
        checksum = jax.lax.fori_loop(
            0, repeats, body, jnp.float32(0.0))

    sums_ref[...] = jnp.where(iota_n == i, checksum, sums_ref[...])
    del row


def membench_pallas(
    buf: jax.Array,   # (rows, LANE) f32; rows >= n_steps for noncontentious
    n_steps: int,
    *,
    contentious: bool,
    write: bool,
    repeats: int = 16,
    interpret: Optional[bool] = None,
):
    """Returns (final_buffer, per-step checksums)."""
    rows = buf.shape[0]
    assert buf.shape[1] == LANE
    if not contentious:
        assert rows >= n_steps, "need one row per grid step"
    n_pad = max(128, -(-n_steps // 128) * 128)

    kernel = functools.partial(
        membench_kernel, contentious=contentious, write=write,
        repeats=repeats)
    full = pl.BlockSpec((rows, LANE), lambda i: (0, 0))
    out_buf, sums = pl.pallas_call(
        kernel,
        grid=(n_steps,),
        in_specs=[full],
        out_specs=(full, pl.BlockSpec((1, n_pad), lambda i: (0, 0))),
        out_shape=(
            jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=kernels.resolve_interpret(interpret),
    )(buf.astype(jnp.float32))
    return out_buf, sums[0, :n_steps]
