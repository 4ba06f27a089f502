"""Plain float32 building blocks of the reference, and its training step.

Written from the published equations as the program states them, with no
import of the program.  Every product runs at ``Precision.HIGHEST``.  The
``Ops`` object is the one switch between the reference and its control:
``Ops(low=True)`` computes every product in float8, the precision below
the configuration's bfloat16, by the usual recipe: both operands rounded
to e4m3 in the forward pass, and the gradient that flows back into each
operand rounded to e5m2, each tensor with one absmax scale.  Everything
else stays float32.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round(x, dtype):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / s).astype(dtype).astype(x.dtype) * s


@jax.custom_vjp
def fp8(x):
    return _round(x, jnp.float8_e4m3fn)


fp8.defvjp(lambda x: (fp8(x), None),
           lambda _, g: (_round(g, jnp.float8_e5m2),))


@dataclass(frozen=True)
class Ops:
    low: bool = False

    def q(self, x):
        return fp8(x) if self.low else x

    def mm(self, a, b):
        return jnp.matmul(self.q(a), self.q(b), precision=HIGHEST)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b), precision=HIGHEST)


def stored(x, dtype):
    """float32 ``x`` rounded to ``dtype`` (bfloat16 or float32), to nearest
    even, by integer arithmetic: a compiler allowed excess precision may
    drop a pair of converts, and then a stored parameter would keep bits
    the configuration's dtype does not hold."""
    if jnp.dtype(dtype) == jnp.float32:
        return x
    if jnp.dtype(dtype) != jnp.bfloat16:
        raise ValueError(f"no rounding to {dtype}")
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    b = (b + jnp.uint32(0x7FFF) + ((b >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def divisor(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is at most ``cap``."""
    return max(d for d in range(1, min(n, cap) + 1) if n % d == 0)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotate interleaved pairs (x[2i], x[2i+1]) of the last axis by
    position * theta**(-2i/d); x: (B, H, S, d), positions 0..S-1."""
    d, S = x.shape[-1], x.shape[-2]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def heads(x, n):
    B, S, D = x.shape
    return x.reshape(B, S, n, D // n).transpose(0, 2, 1, 3)


def merge(x):
    B, H, S, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * d)


def attention(q, k, v, causal: bool, ops: Ops, block: int = 512):
    """softmax(q k^T / sqrt(d)) v over all keys, in blocks of query rows.
    q: (B, H, Sq, d); k, v: (B, H, Sk, d)."""
    Sq, d = q.shape[2], q.shape[3]
    Sk = k.shape[2]
    c = divisor(Sq, block)

    @jax.checkpoint
    def one(args):
        qb, i = args
        s = ops.einsum("bhqd,bhkd->bhqk", qb, k) / math.sqrt(d)
        if causal:
            rows = i * c + jnp.arange(c)
            s = jnp.where(rows[:, None] >= jnp.arange(Sk)[None, :], s, -jnp.inf)
        return ops.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)

    qb = q.reshape(*q.shape[:2], Sq // c, c, d).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(one, (qb, jnp.arange(Sq // c)))
    return out.transpose(1, 2, 0, 3, 4).reshape(q.shape)


def swiglu(p, x, ops: Ops):
    return ops.mm(jax.nn.silu(ops.mm(x, p["wg"])) * ops.mm(x, p["wi"]), p["wo"])


def xent_sum(x, w, labels, mask, vocab: int, ops: Ops, block: int = 512):
    """Sum over tokens of mask * (logsumexp(logits) - logit[label]); logits
    x @ w over the first ``vocab`` columns (the rest of ``w`` is padding)."""
    D = x.shape[-1]
    x = x.reshape(-1, D)
    labels, mask = labels.reshape(-1), mask.reshape(-1)
    c = divisor(x.shape[0], block)
    live = jnp.arange(w.shape[1]) < vocab

    @jax.checkpoint
    def one(args):
        xc, lc, mc = args
        logits = jnp.where(live, ops.mm(xc, w), -jnp.inf)
        tgt = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(mc * (jax.nn.logsumexp(logits, axis=-1) - tgt))

    parts = jax.lax.map(one, (x.reshape(-1, c, D), labels.reshape(-1, c),
                              mask.reshape(-1, c)))
    return jnp.sum(parts)


def scan_layers(body, x, stack):
    """Apply ``body(x, layer_params)`` over the leading axis of ``stack``,
    recomputing each layer in the backward pass."""
    def step(h, lp):
        return jax.checkpoint(body)(h, lp), None
    return jax.lax.scan(step, x, stack)[0]


def leaf_names(tree):
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(l.astype(jnp.float32).reshape(-1))
                      for l in jax.tree_util.tree_leaves(tree)])
