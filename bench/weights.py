"""Seeded initial weights, made by the benchmark on the device in one call.

The tree's layout comes from the configuration's reference
(``param_shapes``); the harness checks it against the program's own before
a run.  Leaf ``i`` (in flattened order) draws from ``fold_in(key, i)``:
norm gains are ones, the embedding is normal * 0.02, and every other matrix
is normal / sqrt(fan-in), fan-in being its second-to-last axis.  Values are
rounded once to the stored dtype.  The same seed gives the same weights in
every program that calls this, so the reference and the delta of the
parameters remake them instead of keeping a copy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import leaf_names


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def base_key(seed: int):
    """A key for any seed up to 64 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def init_leaf(key, name: str, shape, dtype):
    last = name.split("/")[-2] if "/" in name else name
    if last.startswith("ln") or last.endswith("norm"):
        x = jnp.ones(shape, jnp.float32)
    elif last == "embed":
        x = jax.random.normal(key, shape, jnp.float32) * 0.02
    else:
        x = jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(shape[-2])
    return x.astype(dtype)


def init_tree(key, shapes: dict, dtype):
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_shape)
    names = leaf_names(jax.tree_util.tree_unflatten(treedef, [0] * len(leaves)))
    return jax.tree_util.tree_unflatten(treedef, [
        init_leaf(jax.random.fold_in(key, i), n, s, dtype)
        for i, (n, s) in enumerate(zip(names, leaves))])


def maker(shapes: dict, dtype, seed: int, sharding):
    """A function of no arguments that makes the weights on ``sharding``'s
    devices (replicated) in one jitted call."""
    fn = jax.jit(lambda k: init_tree(k, shapes, jnp.dtype(dtype)),
                 out_shardings=sharding)
    key = base_key(seed)
    return lambda: fn(key)
