"""The plain reference of a training cell: three steps of loss, gradient,
clipping and AdamW, in float32 at the highest matmul precision.

It imports nothing of the program.  It takes the configuration file, the
seeded initial weights and the batches that the benchmark itself made, and
computes the whole global batch in blocks of rows, data-parallel over the
cell's chips, so that it fits beside nothing else once the program's state
is freed.  Parameters are stored between steps in the configuration's
dtype, as the configuration states; every operation on them is float32.
"""
from __future__ import annotations

import functools
import importlib
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.reference.common import Ops, leaf_norms, stored


def family(config: dict):
    return importlib.import_module(f"bench.reference.{config['family']}")


def _block_rows(B: int, chips: int) -> int:
    """Rows per block: at most 8 per chip, a multiple of the chip count."""
    per = max(d for d in range(1, min(8, B // chips) + 1) if (B // chips) % d == 0)
    return per * chips


def train(config: dict, make_params: Callable, batches: List[Dict[str, np.ndarray]],
          mesh, *, low: bool = False, loss_rows: Optional[int] = None,
          grad_rows: Optional[int] = None, half_tokens: bool = False,
          grad_scale: float = 1.0) -> dict:
    """Run ``len(batches)`` steps from ``make_params()``, the seeded initial
    weights in their stored dtype, placed on ``mesh``.

    ``loss_rows`` / ``grad_rows``: the loss, and the gradient, are the mean
    over the batch's first that many rows (default: all).  ``half_tokens``:
    the mean over the first half of every row's tokens.  ``grad_scale``
    multiplies the gradient before clipping (a sum over chips where a mean
    is due).  Those plant a fault in the reference put in the program's
    place; ``low`` computes it in float8 (the control).

    Returns per step the loss and the global gradient norm before clipping,
    and per leaf: the norm of the first step's clipped gradient, the norm
    of its raw gradient, and the norm of the change of the parameters over
    all the steps.
    """
    fam, ops, opt = family(config), Ops(low=low), config["optimizer"]
    dtype = config["assumed"]["dtype"]
    split = NamedSharding(mesh, P("data"))
    chips = mesh.size
    f32 = lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)

    def masked_sum(p32, blk, mask):
        return fam.loss_sum(p32, {**blk, "mask": mask}, config, ops)

    block_loss_grad = jax.jit(jax.value_and_grad(masked_sum))
    block_loss = jax.jit(masked_sum)
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adamw(params, m, v, g_sum, n, t):
        b1, b2 = opt["b1"], opt["b2"]
        g = jax.tree_util.tree_map(lambda x: x * (grad_scale / n), g_sum)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
        raw = leaf_norms(g)
        g = jax.tree_util.tree_map(
            lambda x: x * jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9)), g)

        def one(p, m, v, g):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["eps"])
            if p.ndim >= 2:
                u = u + opt["weight_decay"] * p
            return stored(p - opt["lr"] * u, dtype), m, v

        out = jax.tree_util.tree_map(one, params, m, v, g)
        pick = lambda i: jax.tree_util.tree_map(lambda p, o: o[i], params, out)
        return pick(0), pick(1), pick(2), gnorm, raw, leaf_norms(g)

    B, S = batches[0]["tokens"].shape
    loss_rows = loss_rows or B
    grad_rows = grad_rows or B
    blk = _block_rows(B, chips)
    if loss_rows % blk or grad_rows % blk:
        blk = chips
    tok_mask = np.ones((B, S), np.float32)
    if half_tokens:
        tok_mask[:, S // 2:] = 0.0

    params = jax.jit(f32)(make_params())
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
    m, v = zeros(params), zeros(params)
    out: dict = {"losses": [], "grad_norms": []}
    for t, batch in enumerate(batches, start=1):
        g_sum, l_sum = None, 0.0
        for r0 in range(0, max(loss_rows, grad_rows), blk):
            part = jax.device_put({k: batch[k][r0:r0 + blk] for k in batch}, split)
            mask = jax.device_put(tok_mask[r0:r0 + blk], split)
            if r0 < grad_rows:
                l, g = block_loss_grad(params, part, mask)
                g_sum = g if g_sum is None else add(g_sum, g)
                del g
            else:
                l = block_loss(params, part, mask)
            if r0 < loss_rows:
                l_sum += float(l)
        n_grad = float(tok_mask[:grad_rows].sum())
        params, m, v, gnorm, raw, g_leaf = adamw(params, m, v, g_sum, np.float32(n_grad),
                                                 np.float32(t))
        out["losses"].append(l_sum / float(tok_mask[:loss_rows].sum()))
        out["grad_norms"].append(float(gnorm))
        if t == 1:
            out["raw_grad_leaf"], out["grad_leaf"] = np.asarray(raw), np.asarray(g_leaf)
    del m, v
    out["update_leaf"] = np.asarray(jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(lambda x, y: x - y.astype(jnp.float32), a, b)))(
            params, make_params()))
    return out
