"""Declarative parameter specs.

Modules declare parameters as `Spec(shape, logical_axes, init)` trees; the
same tree materializes real arrays (smoke tests / training), abstract
ShapeDtypeStructs with NamedShardings (multi-pod dry-run), or PartitionSpec
trees (jit in_shardings) — one source of truth for shape + sharding.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..pshard import ShardingRules, ambient_rules, spec_for

__all__ = ["Spec", "materialize", "abstractify", "partition_specs", "count_params"]


@dataclasses.dataclass(frozen=True)
class Spec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]       # logical axis names, len == ndim
    init: str = "normal"                  # normal | zeros | ones | scaled
    scale: float = 0.02
    dtype: Optional[str] = None           # None -> caller's default dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)

    def resolved_dtype(self, default):
        return jnp.dtype(self.dtype) if self.dtype is not None else jnp.dtype(default)


def _is_spec(x) -> bool:
    return isinstance(x, Spec)


def materialize(key: jax.Array, tree: Any, dtype=jnp.float32) -> Any:
    """Create real parameter arrays from a Spec tree.  Each leaf is drawn
    by one compiled program, so a bfloat16 leaf leaves no float32 copy
    behind in device memory."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten([_init_leaf(k, s, s.resolved_dtype(dtype))
                              for k, s in zip(keys, leaves)])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init_leaf(key: jax.Array, s: Spec, dt) -> jax.Array:
    if s.init == "zeros":
        return jnp.zeros(s.shape, dt)
    if s.init == "ones":
        return jnp.ones(s.shape, dt)
    # the barrier keeps XLA from folding the scale into the draw's own
    # constants, so the values are bit-identical to drawing eagerly
    x = jax.lax.optimization_barrier(jax.random.normal(key, s.shape))
    if s.init == "scaled":
        fan_in = s.shape[0] if len(s.shape) > 1 else max(s.shape[0], 1)
        return (x / jnp.sqrt(jnp.float32(fan_in))).astype(dt)
    return (s.scale * x).astype(dt)


def abstractify(tree: Any, mesh, dtype=jnp.float32,
                rules: Optional[ShardingRules] = None) -> Any:
    """ShapeDtypeStruct tree with NamedShardings (no allocation; dry-run)."""
    from jax.sharding import NamedSharding

    def conv(s: Spec):
        spec = spec_for(s.shape, s.axes, mesh, rules)
        return jax.ShapeDtypeStruct(s.shape, s.resolved_dtype(dtype),
                                    sharding=NamedSharding(mesh, spec))
    return jax.tree.map(conv, tree, is_leaf=_is_spec)


def partition_specs(tree: Any, mesh, rules: Optional[ShardingRules] = None) -> Any:
    return jax.tree.map(lambda s: spec_for(s.shape, s.axes, mesh, rules),
                        tree, is_leaf=_is_spec)


def count_params(tree: Any) -> int:
    tot = 0
    for s in jax.tree.leaves(tree, is_leaf=_is_spec):
        n = 1
        for d in s.shape:
            n *= d
        tot += n
    return tot
