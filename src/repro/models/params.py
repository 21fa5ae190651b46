"""Single-source-of-truth parameter definitions.

Model code builds a pytree of ParamDef (shape + LOGICAL axis names + init).
From that one tree we derive:
  * materialized parameters        (init_params)
  * PartitionSpecs for pjit        (parallel.sharding.defs_to_pspecs)
  * analytic byte/param counts     (configs, roofline)
Keeping shapes and shardings in one place is what makes 40 (arch × shape)
dry-run cells maintainable.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis name per dim
    init: str = "normal"              # normal | zeros | ones | small_normal
    fan_in_axes: Tuple[int, ...] = () # dims whose product is fan-in
    dtype: str = "float32"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1


def _fan_in(d: ParamDef) -> int:
    if not d.fan_in_axes:
        return d.shape[-2] if len(d.shape) >= 2 else max(d.shape[-1], 1)
    f = 1
    for ax in d.fan_in_axes:
        f *= d.shape[ax]
    return f


def init_leaf(d: ParamDef, key: jax.Array) -> jax.Array:
    """Materialize one ParamDef (the per-leaf body of `init_params`)."""
    dt = jnp.dtype(d.dtype)
    if d.init == "zeros":
        return jnp.zeros(d.shape, dt)
    if d.init == "ones":
        return jnp.ones(d.shape, dt)
    if d.init == "arange_neg":   # mamba A_log init: log(1..16) style
        h = d.shape[-1]
        base = jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32))
        return jnp.broadcast_to(base, d.shape).astype(dt)
    std = 1.0 / math.sqrt(_fan_in(d))
    if d.init == "small_normal":
        std *= 0.1
    return (jax.random.truncated_normal(key, -3, 3, d.shape, jnp.float32)
            * std).astype(dt)


def init_params(defs, key: jax.Array):
    """Materialize a ParamDef tree (layout-preserving)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    vals = [init_leaf(d, k) for d, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def abstract_params(defs):
    """ShapeDtypeStruct tree — for .lower() without allocating (dry-run)."""
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype)),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


def count_params(defs) -> int:
    return sum(d.size for d in jax.tree_util.tree_leaves(
        defs, is_leaf=lambda x: isinstance(x, ParamDef)))


def param_bytes(defs) -> int:
    return sum(d.size * jnp.dtype(d.dtype).itemsize
               for d in jax.tree_util.tree_leaves(
                   defs, is_leaf=lambda x: isinstance(x, ParamDef)))
