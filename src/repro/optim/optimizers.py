"""Optimizers in pure JAX (no optax dependency): SGD, momentum, AdamW.

State layout mirrors the param pytree.  The trainer
(``repro.launch.train``) replicates it on every device, like the params.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

Params = Any


class OptState(NamedTuple):
    count: jnp.ndarray
    mu: Params          # first moment (or momentum); zeros pytree for sgd
    nu: Params          # second moment; zeros pytree for sgd/momentum


@dataclass(frozen=True)
class Optimizer:
    name: str
    init: Callable[[Params], OptState]
    update: Callable[[Params, OptState, Params, float], Tuple[Params, OptState]]


def _zeros_like_f32(params: Params) -> Params:
    return jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _empty(params: Params) -> Params:
    return jax.tree_util.tree_map(lambda p: jnp.zeros((), jnp.float32), params)


def sgd() -> Optimizer:
    def init(params):
        return OptState(jnp.zeros((), jnp.int32), _empty(params), _empty(params))

    def update(params, state, grads, lr):
        new_p = jax.tree_util.tree_map(
            lambda p, g: (p.astype(jnp.float32) - lr * g.astype(jnp.float32)).astype(p.dtype),
            params, grads)
        return new_p, state._replace(count=state.count + 1)

    return Optimizer("sgd", init, update)


def momentum(beta: float = 0.9) -> Optimizer:
    def init(params):
        return OptState(jnp.zeros((), jnp.int32), _zeros_like_f32(params), _empty(params))

    def update(params, state, grads, lr):
        mu = jax.tree_util.tree_map(
            lambda m, g: beta * m + g.astype(jnp.float32), state.mu, grads)
        new_p = jax.tree_util.tree_map(
            lambda p, m: (p.astype(jnp.float32) - lr * m).astype(p.dtype), params, mu)
        return new_p, OptState(state.count + 1, mu, state.nu)

    return Optimizer("momentum", init, update)


def adamw(b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
          weight_decay: float = 0.1) -> Optimizer:
    def init(params):
        return OptState(jnp.zeros((), jnp.int32),
                        _zeros_like_f32(params), _zeros_like_f32(params))

    def update(params, state, grads, lr):
        count = state.count + 1
        c = count.astype(jnp.float32)
        bc1 = 1.0 - b1 ** c
        bc2 = 1.0 - b2 ** c

        def upd(p, g, m, v):
            g = g.astype(jnp.float32)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * jnp.square(g)
            mhat = m / bc1
            vhat = v / bc2
            step = mhat / (jnp.sqrt(vhat) + eps)
            if p.ndim >= 2:  # decoupled weight decay on matrices only
                step = step + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * step).astype(p.dtype), m, v

        flat_p, tdef = jax.tree_util.tree_flatten(params)
        flat_g = tdef.flatten_up_to(grads)
        flat_m = tdef.flatten_up_to(state.mu)
        flat_v = tdef.flatten_up_to(state.nu)
        out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = tdef.unflatten([o[0] for o in out])
        mu = tdef.unflatten([o[1] for o in out])
        nu = tdef.unflatten([o[2] for o in out])
        return new_p, OptState(count, mu, nu)

    return Optimizer("adamw", init, update)


def get_optimizer(name: str, **kw) -> Optimizer:
    return {"sgd": sgd, "momentum": momentum, "adamw": adamw}[name](**kw)
