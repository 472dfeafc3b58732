"""Preallocated KV slot arena for continuous-batching serving.

One replica owns a fixed ``[K, max_len, ...]`` decode-cache arena — K is
the concurrency budget, the same K that parameterizes the Algorithm-5
admission semaphore. A request occupies exactly one slot (one batch row
of every cache leaf) from admission to retirement; eviction is O(1)
free-list bookkeeping, and the arena itself is never reallocated, so the
engine's batched ``decode_step`` always runs at a fixed shape.

The pool is model-agnostic: it derives the arena from
``model.init_cache(K, max_len)`` and auto-detects each leaf's batch axis
by diffing the leaf shapes of a batch-1 vs batch-2 cache (periods-stacked
KV leaves carry the batch on axis 1, leftover/mamba-state leaves on
axis 0, encoder-decoder leaves on axis 1 — the pool does not hard-code
any of this). ``insert`` writes a prefilled single-request cache into a
slot with one jitted ``dynamic_update_slice`` per leaf.

``cache["len"]`` becomes a per-slot ``[K]`` int32 vector — the model's
decode path accepts vector lengths (models/blocks.block_decode) so each
row attends at its own depth.
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp

PyTree = Any


def _split_len(cache):
    """(cache-without-len, len-leaf). The length vector is engine-owned
    state with its own update rule, so it is excluded from the generic
    per-leaf batch-axis machinery."""
    rest = {k: v for k, v in cache.items() if k != "len"}
    return rest, cache.get("len")


def batch_axes(model, max_len: int) -> List[int]:
    """Batch axis of every (flattened, 'len'-stripped) cache leaf,
    detected by diffing batch-1 vs batch-2 ShapeDtypeStruct caches.

    A leaf where some *non-batch* dim coincidentally also differs between
    the two probes (e.g. a bucketed scratch dim that rounds differently
    at batch 1) is disambiguated with a second batch-2 vs batch-3 probe:
    the batch axis is the one that moves under both probes. Only a leaf
    that stays ambiguous under the intersection raises.
    """
    def leaves(b):
        rest, _ = _split_len(model.init_cache(b, max_len, for_shapes=True))
        return jax.tree_util.tree_leaves(rest)

    def diff(a, b):
        return {i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y}

    l1, l2 = leaves(1), leaves(2)
    l3 = None
    axes = []
    for i, (a, b) in enumerate(zip(l1, l2)):
        d = diff(a, b)
        if len(d) != 1:
            if l3 is None:
                l3 = leaves(3)
            d = d & diff(b, l3[i])
        if len(d) != 1:
            raise ValueError(
                f"cannot locate batch axis for cache leaf {a.shape}")
        axes.append(d.pop())
    return axes


class SlotPool:
    """Fixed-capacity KV arena + free-list (insert / evict / per-slot len).

    The free list is FIFO (slot reuse order is deterministic), matching
    the FIFO handoff of the sleeping semaphore that gates admission.
    """

    def __init__(self, model, capacity: int, max_len: int):
        if capacity < 1:
            raise ValueError("slot pool capacity must be >= 1")
        self.capacity = capacity
        self.max_len = max_len
        self._axes = batch_axes(model, max_len)
        arena, _ = _split_len(model.init_cache(capacity, max_len))
        self._treedef = jax.tree_util.tree_structure(arena)
        self.arena: PyTree = arena
        # per-slot sequence length; retired rows keep drifting harmlessly
        # (their writes drop once out of range) until the slot is reused
        self.lens: jax.Array = jnp.zeros((capacity,), jnp.int32)
        self._free: List[int] = list(range(capacity))
        self._rid: List[Optional[int]] = [None] * capacity
        self._insert_jit = jax.jit(self._insert_impl)

    # ------------------------------------------------------------- free list
    @property
    def virtual_max_len(self) -> int:
        """Longest context one slot can hold (== the physical row here;
        the paged layout decouples the two)."""
        return self.max_len

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return self.capacity - len(self._free)

    def active_slots(self) -> List[int]:
        return [s for s, r in enumerate(self._rid) if r is not None]

    def rid_of(self, slot: int) -> Optional[int]:
        return self._rid[slot]

    def acquire(self, rid: int) -> int:
        """Claim the next free slot (FIFO reuse order) for request rid."""
        if not self._free:
            raise RuntimeError("slot pool exhausted — admission must gate "
                               "on the semaphore before acquiring")
        slot = self._free.pop(0)
        self._rid[slot] = rid
        return slot

    def evict(self, slot: int) -> None:
        """Retire a slot; the stale cache row is overwritten on reuse."""
        if self._rid[slot] is None:
            raise RuntimeError(f"evicting free slot {slot}")
        self._rid[slot] = None
        self._free.append(slot)

    # --------------------------------------------------------------- device
    def _insert_impl(self, arena, lens, req_cache, slot, length):
        la = jax.tree_util.tree_leaves(arena)
        lr = jax.tree_util.tree_leaves(req_cache)
        out = [
            jax.lax.dynamic_update_slice_in_dim(
                a, r.astype(a.dtype), slot, axis=ax)
            for a, r, ax in zip(la, lr, self._axes)
        ]
        return (jax.tree_util.tree_unflatten(self._treedef, out),
                lens.at[slot].set(length))

    def insert(self, slot: int, req_cache: PyTree, length,
               reserve: Optional[int] = None) -> None:
        """Write a prefilled batch-1 request cache into ``slot``.

        ``reserve`` (total tokens the request may ever occupy) is a
        paged-layout concern; the contiguous arena always holds a full
        ``max_len`` row, so it is accepted and ignored here.
        """
        del reserve
        req, _ = _split_len(req_cache)
        self.arena, self.lens = self._insert_jit(
            self.arena, self.lens, req,
            jnp.asarray(slot, jnp.int32), jnp.asarray(length, jnp.int32))

    def assign(self, slot: int, length: int = 0) -> None:
        """Initialize ``slot`` for chunked prefill without writing the
        arena: the chunk dispatches scatter K/V directly into the slot's
        row at the engine's cursor, so admission only has to reset the
        length vector (the stale row beyond ``length`` is rewritten
        before anything reads it — scatter-then-attend)."""
        self.lens = self.lens.at[int(slot)].set(int(length))

    def cache_view(self) -> PyTree:
        """The arena in model-cache form (arena leaves + 'len' vector).
        Consumed by the round dispatch it is handed to, as
        ``PagedSlotPool.cache_view``: only :meth:`adopt` hands the arena
        back."""
        out = dict(self.arena)
        out["len"] = self.lens
        return out

    def adopt(self, cache: PyTree) -> None:
        """Take back the post-decode cache (as returned by decode_step on
        a ``cache_view()``): arena leaves + advanced 'len' vector."""
        cache = dict(cache)
        lens = cache.pop("len")
        self.arena = cache
        self.set_lens(lens)

    def set_lens(self, lens: jax.Array) -> None:
        """Adopt the post-decode length vector (engine calls this after
        each batched decode iteration advanced active rows)."""
        self.lens = lens
