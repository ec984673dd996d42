"""Weights made on the device from the seed, in one jitted call.

The shapes come from the program's parameter tree (abstractly, nothing is
computed); the values come from here, so the reference and the program are
handed the same arrays and the reference takes nothing the program made.
Every leaf is drawn by its role: matmul and conv kernels as normal /
sqrt(fan in), biases small, norm scales near one, embeddings small.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _draw(name: str, shape, key):
    """``shape[0]`` leaves of one role and shape, drawn in one call."""
    n = jax.random.normal(key, shape, jnp.float32)
    if name == "kernel":
        fan_in = 1
        for s in shape[1:-1]:
            fan_in *= s
        return n / jnp.sqrt(jnp.float32(fan_in))
    if name == "bias":
        return 0.02 * n
    if name == "scale":
        return 1.0 + 0.1 * n
    if name == "table":
        return 0.02 * n
    if name == "pos":
        return 0.01 * n
    raise ValueError(f"no rule to draw parameter {name!r}")


def _name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def make(shapes, seed: int):
    """``shapes``: a pytree of ShapeDtypeStruct.  Returns device arrays.

    Leaves that share a role, a shape and a dtype are drawn together, one
    random call per group, so the program stays small and compiles fast."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    groups: dict = {}
    for i, (path, s) in enumerate(flat):
        groups.setdefault((_name(path), s.shape, jnp.dtype(s.dtype).name),
                          []).append(i)

    def build(key):
        leaves = [None] * len(flat)
        for g, ((name, shape, dtype), idx) in enumerate(sorted(groups.items())):
            vals = _draw(name, (len(idx),) + shape,
                         jax.random.fold_in(key, g)).astype(dtype)
            for j, i in enumerate(idx):
                leaves[i] = vals[j]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(jax.random.PRNGKey(seed))
