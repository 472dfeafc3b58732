"""Parameter-tree machinery shared by every architecture.

Parameters are plain pytrees (nested dicts of arrays). Each model first
builds a *spec tree* of ``ArraySpec`` — shape, dtype, initializer and
**logical axis names** — from which we derive, without ever materializing
weights:

  * ``init_params``      — random init (smoke tests, examples, real runs)
  * ``shape_params``     — ShapeDtypeStructs (the multi-pod dry-run)
  * ``logical_tree``     — logical axes per leaf (sharding/rules.py maps
                            them onto the mesh)

Logical axis vocabulary (see sharding/rules.py for the mesh mapping):
  'batch' 'seq' 'embed' 'heads' 'kv_heads' 'head_dim' 'mlp' 'vocab'
  'expert' 'layer' (scan-stacked leading axis) 'conv' 'state' 'dt'
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

PyTree = Any
Logical = Tuple[Optional[str], ...]


@dataclasses.dataclass(frozen=True)
class ArraySpec:
    shape: Tuple[int, ...]
    dtype: Any
    logical: Logical
    init: str = "normal"      # normal | zeros | ones | scaled
    init_scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def spec(shape, logical, dtype=jnp.bfloat16, init="normal", scale=1.0):
    return ArraySpec(tuple(int(s) for s in shape), dtype, tuple(logical),
                     init, scale)


def is_spec(x) -> bool:
    return isinstance(x, ArraySpec)


def tree_map_specs(fn: Callable[[ArraySpec], Any], tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(fn, tree, is_leaf=is_spec)


def shape_params(spec_tree: PyTree) -> PyTree:
    """ShapeDtypeStructs for the dry-run — zero bytes allocated."""
    return tree_map_specs(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), spec_tree)


def logical_tree(spec_tree: PyTree) -> PyTree:
    return tree_map_specs(lambda s: s.logical, spec_tree)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal_leaf(key, std, *, shape, dtype):
    # one compiled draw-scale-cast: XLA fuses the float32 normal into
    # the cast, so a bf16 leaf never exists as a whole float32 copy (a
    # 12-layer stacked MLP leaf at d_model 5120 would be a 4.3 GB
    # transient)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_params(spec_tree: PyTree, key: jax.Array) -> PyTree:
    """Materialize parameters. Fan-in-scaled normal for weights."""
    leaves, treedef = jax.tree_util.tree_flatten(spec_tree, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))

    def one(s: ArraySpec, k):
        if s.init == "zeros":
            return jnp.zeros(s.shape, s.dtype)
        if s.init == "ones":
            return jnp.ones(s.shape, s.dtype)
        fan_in = s.shape[0] if len(s.shape) > 1 else max(s.shape[-1], 1)
        std = s.init_scale / math.sqrt(max(fan_in, 1))
        if jnp.dtype(s.dtype) == jnp.float32:
            # no cast, so no transient: drawn eagerly as always, because
            # fusing the scale into the draw moves the last bit and the
            # float32 test configs keep their exact values
            return jax.random.normal(k, s.shape, jnp.float32) * std
        return _normal_leaf(k, jnp.float32(std), shape=s.shape,
                            dtype=jnp.dtype(s.dtype))

    return jax.tree_util.tree_unflatten(
        treedef, [one(s, k) for s, k in zip(leaves, keys)])


def stack_specs(spec_tree: PyTree, n: int) -> PyTree:
    """Add a leading 'layer' axis of size n (for lax.scan over layers)."""
    return tree_map_specs(
        lambda s: ArraySpec((n,) + s.shape, s.dtype, ("layer",) + s.logical,
                            s.init, s.init_scale),
        spec_tree)


def count_params(spec_tree: PyTree) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(spec_tree, is_leaf=is_spec):
        total += math.prod(leaf.shape)
    return total


# ------------------------------------------------------------- activations
def activation(name: str):
    if name == "swiglu":        # handled at the MLP level (gated)
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu2":         # squared relu (nemotron/minitron family)
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "silu":
        return jax.nn.silu
    raise ValueError(name)


def cast(x, dtype):
    return x.astype(dtype) if x.dtype != dtype else x
