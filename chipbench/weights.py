"""Seeded random weights, drawn on the device in one jitted call.

The tree's shapes come from the program's parameter declarations (the
system under test decides its own layout); the values are the
benchmark's: every leaf is random, so no part of the model is an identity
the comparison could not see.  Matrices get a standard deviation of
1/sqrt(fan-in) (fan-in = the second-to-last axis, so a stacked layer axis
does not shrink them), a declared scale wins, ``ones`` leaves are
1 + N(0, 0.1) and ``zeros`` leaves N(0, 0.02) -- biases, the layernorm1p
offsets and the LoRA up-projection included.
"""

from __future__ import annotations

import math


def leaf_std(pd) -> tuple[float, float]:
    """(mean, standard deviation) of one declared leaf's values."""
    if pd.init == "ones":
        return 1.0, 0.1
    if pd.init == "zeros":
        return 0.0, 0.02
    if pd.scale is not None:
        return 0.0, float(pd.scale)
    fan_in = pd.shape[-2] if len(pd.shape) >= 2 else pd.shape[-1]
    return 0.0, 1.0 / math.sqrt(fan_in)


def seed_key(seed: int):
    """A PRNG key for any whole number, 64-bit seeds included."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def draw(decls, seed: int, dtype):
    """The parameter tree for ``decls`` in ``dtype``, from ``seed``."""
    import jax
    import jax.numpy as jnp

    from repro.models.params import PD

    leaves, treedef = jax.tree.flatten(decls,
                                       is_leaf=lambda x: isinstance(x, PD))
    dt = jnp.dtype(dtype)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for pd, k in zip(leaves, keys):
            mean, std = leaf_std(pd)
            x = jax.random.normal(k, pd.shape, dt) * jnp.asarray(std, dt)
            out.append(x + jnp.asarray(mean, dt) if mean else x)
        return treedef.unflatten(out)

    return jax.jit(make)(seed_key(seed))
