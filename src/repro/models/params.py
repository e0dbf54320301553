"""Declarative parameter system (the framework's flax replacement).

A model describes its parameters once, as a nested dict of ``PD`` leaves
(shape + logical sharding axes + init style).  Three materializations share
that single description:

  * ``init_params``      -> concrete jnp arrays (seeded, per-leaf fold_in)
  * ``abstract_params``  -> jax.ShapeDtypeStruct stand-ins (dry-run, zero alloc)
  * ``logical_axes``     -> pytree of logical-axis tuples for the sharding rules

Scan-stacked layers simply declare a leading "layers" dimension.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PD:
    """One parameter declaration."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"        # normal | zeros | ones | embed
    scale: float | None = None  # stddev override; default 1/sqrt(fan_in)
    dtype: Any = jnp.float32    # master dtype (compute casts separately)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_pd(x) -> bool:
    return isinstance(x, PD)


def _fan_in(shape: tuple[int, ...]) -> int:
    # heuristics: last dim is fan-out, the product of the rest (minus any
    # leading layer-stack dim handled by callers passing explicit scale).
    if len(shape) == 1:
        return shape[0]
    fan = 1
    for d in shape[:-1]:
        fan *= d
    return max(fan, 1)


def init_params(decls, key: jax.Array, dtype=None):
    """Materialize concrete parameters; every leaf gets a distinct key.

    ``dtype`` (e.g. ``RunConfig.param_dtype``) overrides the declared
    master dtype.  Each leaf is drawn directly in its dtype, one leaf at a
    time, so a bf16 tree never holds an f32 copy of itself."""
    leaves, treedef = jax.tree.flatten(decls, is_leaf=_is_pd)
    keys = jax.random.split(key, max(len(leaves), 1))

    def make(pd: PD, k: jax.Array):
        dt = jnp.dtype(dtype if dtype is not None else pd.dtype)
        if pd.init == "zeros":
            return jnp.zeros(pd.shape, dt)
        if pd.init == "ones":
            return jnp.ones(pd.shape, dt)
        if pd.init == "embed":
            std = pd.scale if pd.scale is not None else 1.0
        else:
            std = pd.scale if pd.scale is not None \
                else _fan_in(pd.shape) ** -0.5
        return _normal(k, pd.shape, dt, std)

    return treedef.unflatten([make(pd, k) for pd, k in zip(leaves, keys)])


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, dtype, std):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)


def abstract_params(decls):
    """ShapeDtypeStruct tree — used by the dry-run (no allocation)."""
    return jax.tree.map(lambda pd: jax.ShapeDtypeStruct(pd.shape, pd.dtype),
                        decls, is_leaf=_is_pd)


def logical_axes(decls):
    """Pytree of logical-axis tuples, same structure as the params."""
    return jax.tree.map(lambda pd: pd.axes, decls, is_leaf=_is_pd)


def param_count(decls) -> int:
    total = 0
    for pd in jax.tree.leaves(decls, is_leaf=_is_pd):
        n = 1
        for d in pd.shape:
            n *= d
        total += n
    return total


def param_bytes(decls) -> int:
    total = 0
    for pd in jax.tree.leaves(decls, is_leaf=_is_pd):
        n = 1
        for d in pd.shape:
            n *= d
        total += n * jnp.dtype(pd.dtype).itemsize
    return total
