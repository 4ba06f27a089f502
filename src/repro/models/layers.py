"""Core pure-JAX building blocks shared by every architecture.

All parameters are plain nested dicts of ``jnp.ndarray`` so they compose with
``jax.tree_util``, pjit partitioning and the bucketed grad-sync in
``repro.parallel.grad_sync``.  Initializers take an explicit PRNG key.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.utils.hlo import EMBED_GRAD

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init (LeCun style)."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# embedding lookup
# ---------------------------------------------------------------------------

@jax.custom_vjp
def embed_lookup(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """``table[ids]``, in the table's dtype.  Its gradient sums the rows'
    cotangents in float32 and rounds once to the table's dtype: a bfloat16
    scatter-add rounds after every addend, and a frequent token's row then
    loses the small ones (a Zipf batch of 4096 tokens took 7% off one row)."""
    return table[ids]


def _embed_fwd(table, ids):
    return table[ids], (ids, table)


def _embed_bwd(res, g):
    ids, table = res
    with jax.named_scope(EMBED_GRAD):
        grad = jnp.zeros(table.shape, jnp.float32).at[ids].add(g.astype(jnp.float32))
        return grad.astype(table.dtype), None


embed_lookup.defvjp(_embed_fwd, _embed_bwd)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, d) with d even; positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                              # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype, n_layers: int = 0) -> Params:
    """SwiGLU MLP; stacked over a leading layer dim when n_layers > 0."""
    ks = split_keys(key, 3)
    lead = (n_layers,) if n_layers else ()
    return {
        "wi": dense_init(ks[0], lead + (d_model, d_ff), dtype),
        "wg": dense_init(ks[1], lead + (d_model, d_ff), dtype),
        "wo": dense_init(ks[2], lead + (d_ff, d_model), dtype),
    }


def mlp(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.silu(x @ params["wg"]) * (x @ params["wi"])
    return h @ params["wo"]


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def chunked_softmax_xent(x: jnp.ndarray, lm_head: jnp.ndarray,
                         labels: jnp.ndarray, chunk: int = 512,
                         valid_vocab: int = 0) -> jnp.ndarray:
    """Cross-entropy over a huge vocab without materializing (B,S,V).

    x: (B, S, D) final hidden states; lm_head: (D, V); labels: (B, S) int32.
    Scans over S in blocks of ``chunk``; each block computes logits, the
    logsumexp and the target logit, discarding the block logits afterwards.
    ``valid_vocab``: mask logits for padded vocab rows >= this (0 = all valid).
    """
    B, S, D = x.shape
    V = lm_head.shape[-1]
    vocab_mask = (jnp.arange(V) >= valid_vocab) if (valid_vocab and valid_vocab < V) else None
    if S % chunk != 0:
        chunk = S  # fall back to one block for odd smoke shapes
    n_blocks = S // chunk
    xb = x.reshape(B, n_blocks, chunk, D).transpose(1, 0, 2, 3)
    lb = labels.reshape(B, n_blocks, chunk).transpose(1, 0, 2)

    def block(carry, inp):
        xc, lc = inp                                  # (B, chunk, D), (B, chunk)
        logits = (xc @ lm_head).astype(jnp.float32)   # (B, chunk, V)
        if vocab_mask is not None:
            logits = jnp.where(vocab_mask, -1e30, logits)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - tgt), None

    total, _ = jax.lax.scan(block, jnp.zeros((), jnp.float32), (xb, lb))
    return total / (B * S)


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------

def count_params(params: Params) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))


def param_bytes(params: Params) -> int:
    return sum(int(p.size) * p.dtype.itemsize for p in jax.tree_util.tree_leaves(params))


def cast_tree(params: Params, dtype) -> Params:
    return jax.tree_util.tree_map(
        lambda p: p.astype(dtype) if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
