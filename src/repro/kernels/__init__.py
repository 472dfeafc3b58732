# Pallas TPU kernels for the paper's synchronization hot spots, each with
# kernel.py (pl.pallas_call + explicit BlockSpec), ops.py (jit'd wrapper)
# and ref.py (pure-jnp oracle), validated under interpret=True on CPU:
#
#   xf_barrier/   — Xiao-Feng decentralized flag barrier w/ timeout +
#                   straggler bitmap (single-owner masked vector writes)
#   ticket_lock/  — fetch-and-add mutex; FIFO grant order + mutual-exclusion
#                   -sensitive affine chain
#   semaphore/    — sleeping (count/ticket/turn) semaphore as deterministic
#                   K-server FIFO admission planning (used by serving)
#   membench/     — the paper's Section-3 memory benchmarks adapted to TPU
#                   HBM access patterns (contentious/noncontentious x r/w)
#
# Each family also ships a *_window variant (fixed-shape, power-of-2
# bucketed padding via repro.sync.window.WindowedPlanner) for schedulers
# that replan varying-length traces every round. The preferred consumer
# surface is repro.sync.SyncLibrary, which routes to these through the
# backend registry ("kernel" = compiled on a TPU and interpreted
# elsewhere, "ref" = the oracles).

from typing import Optional

import jax


def default_interpret() -> bool:
    """The one place the kernel tier is chosen: Pallas kernels compile
    on a TPU and run under the interpreter on every other platform."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret`` if the caller pinned it, else ``default_interpret()``.
    Every kernel wrapper calls this through the module attribute
    (``kernels.resolve_interpret``), so the tier the sync backends report
    and the tier the kernels run read one binding."""
    return default_interpret() if interpret is None else interpret


from repro.kernels.membench.ops import membench  # noqa: F401
from repro.kernels.semaphore.ops import (  # noqa: F401
    semaphore_admission,
    semaphore_admission_window,
)
from repro.kernels.ticket_lock.ops import (  # noqa: F401
    ticket_lock_run,
    ticket_lock_window,
)
from repro.kernels.xf_barrier.ops import (  # noqa: F401
    fresh_flags,
    xf_barrier,
    xf_barrier_window,
)
