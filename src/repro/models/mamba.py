"""Mamba (S6) selective-state-space mixer — the SSM half of Jamba.

TPU-native adaptation: the recurrence is *chunked* — a ``lax.scan`` over
chunks of ``cfg.ssm.chunk_size`` tokens carries the (d_inner, d_state)
state, and inside each chunk a ``lax.associative_scan`` (logarithmic depth,
maps onto the VPU) computes the per-token states.  This bounds the live
activation to one (B, C, d_inner, d_state) block instead of the full
sequence, which is what lets ``long_500k`` lower.

Parameter names match the sharding rules in ``repro.parallel.sharding``
(everything hangs off an ``"ssm"`` subtree; d_inner is the `model`-sharded
axis).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import Params, dense_init, split_keys


def dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    d_inner = cfg.ssm.expand * cfg.d_model
    dt_rank = cfg.ssm.dt_rank or max(cfg.d_model // 16, 1)
    return d_inner, dt_rank, cfg.ssm.d_state, cfg.ssm.d_conv


def init_mamba(key, cfg: ModelConfig, n_layers: int = 0) -> Params:
    di, dt_rank, n, d_conv = dims(cfg)
    D = cfg.d_model
    ks = split_keys(key, 6)
    lead = (n_layers,) if n_layers else ()
    dtype = jnp.dtype(cfg.dtype)
    # S4D-real initialization for A; dt bias spread over [1e-3, 1e-1]
    a = jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (di, n))
    dt_init = jnp.exp(
        jax.random.uniform(ks[4], lead + (di,), jnp.float32)
        * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))      # inverse softplus
    return {
        "w_in": dense_init(ks[0], lead + (D, 2 * di), dtype),
        "conv_w": dense_init(ks[1], lead + (d_conv, di), dtype, scale=0.5),
        "conv_b": jnp.zeros(lead + (di,), dtype),
        "w_bcdt": dense_init(ks[2], lead + (di, dt_rank + 2 * n), dtype),
        "w_dt": dense_init(ks[3], lead + (dt_rank, di), dtype),
        "dt_bias": dt_bias.astype(jnp.float32) * jnp.ones(lead + (di,), jnp.float32),
        "a_log": jnp.log(a) * jnp.ones(lead + (di, n), jnp.float32),
        "d_skip": jnp.ones(lead + (di,), jnp.float32),
        "w_out": dense_init(ks[5], lead + (di, D), dtype),
    }


# ---------------------------------------------------------------------------
# selective scan (chunked)
# ---------------------------------------------------------------------------

def _ssm_scan_chunked(decay, bx, h0, chunk: int):
    """h_t = decay_t * h_{t-1} + bx_t, computed chunk-at-a-time.

    decay, bx: (B, S, di, n) fp32; h0: (B, di, n).
    Returns (y_states (B, S, di, n), h_final).
    """
    B, S, di, n = decay.shape
    if S % chunk != 0:
        chunk = S
    nc = S // chunk
    dc = decay.reshape(B, nc, chunk, di, n).transpose(1, 0, 2, 3, 4)
    bc = bx.reshape(B, nc, chunk, di, n).transpose(1, 0, 2, 3, 4)

    def combine(a, b):
        return (a[0] * b[0], a[1] * b[0] + b[1])

    def step(h, inp):
        d, b = inp                                     # (B, chunk, di, n)
        cum_d, inner = jax.lax.associative_scan(combine, (d, b), axis=1)
        states = inner + cum_d * h[:, None]
        return states[:, -1], states

    h_final, states = jax.lax.scan(step, h0, (dc, bc))
    states = states.transpose(1, 0, 2, 3, 4).reshape(B, S, di, n)
    return states, h_final


def _ssm_scan_chunked_fused_y(decay, bx, c_t, h0, chunk: int):
    """Variant (``cfg.mamba_fused_y``): contract the d_state axis
    against C inside the chunk step, so the scan emits y chunks
    (B, C, di) instead of state chunks (B, C, di, n) — an n-fold (16x)
    reduction of the scan's stacked output and its backward residual.

    decay, bx: (B, S, di, n); c_t: (B, S, n); h0: (B, di, n).
    Returns (y (B, S, di), h_final).
    """
    B, S, di, n = decay.shape
    if S % chunk != 0:
        chunk = S
    nc = S // chunk
    dc = decay.reshape(B, nc, chunk, di, n).transpose(1, 0, 2, 3, 4)
    bc = bx.reshape(B, nc, chunk, di, n).transpose(1, 0, 2, 3, 4)
    cc = c_t.reshape(B, nc, chunk, n).transpose(1, 0, 2, 3)

    def combine(a, b):
        return (a[0] * b[0], a[1] * b[0] + b[1])

    def step(h, inp):
        d, b, ct = inp
        cum_d, inner = jax.lax.associative_scan(combine, (d, b), axis=1)
        states = inner + cum_d * h[:, None]
        y = jnp.einsum("bcdn,bcn->bcd", states, ct)
        return states[:, -1], y

    h_final, yc = jax.lax.scan(step, h0, (dc, bc, cc))
    y = yc.transpose(1, 0, 2, 3).reshape(B, S, di)
    return y, h_final


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ssm_pallas_cv(decay, bx, c_t, h0, chunk):
    """Pallas selective scan with the chunked-jnp path's gradients
    (recompute in backward) — same pattern as the flash-attention dispatch."""
    from repro.kernels.ssm_scan import ssm_scan_pallas
    y, h = ssm_scan_pallas(decay.transpose(0, 1, 3, 2),
                           bx.transpose(0, 1, 3, 2), c_t,
                           h0.transpose(0, 2, 1), chunk=chunk,
                           interpret=jax.default_backend() != "tpu")
    return y, h.transpose(0, 2, 1)


def _ssm_cv_fwd(decay, bx, c_t, h0, chunk):
    return _ssm_pallas_cv(decay, bx, c_t, h0, chunk), (decay, bx, c_t, h0)


def _ssm_cv_bwd(chunk, res, g):
    decay, bx, c_t, h0 = res
    _, vjp = jax.vjp(
        lambda d, b, c, h: _ssm_scan_chunked_fused_y(d, b, c, h, chunk),
        decay, bx, c_t, h0)
    return vjp(g)


_ssm_pallas_cv.defvjp(_ssm_cv_fwd, _ssm_cv_bwd)


def _use_pallas_scan(cfg, S, di) -> bool:
    from repro.models.attention import use_pallas
    return (use_pallas(cfg) and S > 1 and S % cfg.ssm.chunk_size == 0
            and di % 128 == 0)


def _depthwise_conv(x, w, b, prev=None):
    """Causal depthwise conv.  x: (B, S, di); w: (d_conv, di); prev: (B, d_conv-1, di)
    left-context (zeros for a fresh sequence).  Returns (y, new_prev)."""
    d_conv = w.shape[0]
    if prev is None:
        prev = jnp.zeros((x.shape[0], d_conv - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([prev, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(d_conv)) + b
    return y, xp[:, -(d_conv - 1):]


def mamba_mixer(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                state: Dict[str, jnp.ndarray] | None = None
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full-sequence mixer.  x: (B, S, D) -> (out (B, S, D), final state)."""
    B, S, D = x.shape
    di, dt_rank, n, d_conv = dims(cfg)
    xz = x @ params["w_in"]                              # (B, S, 2*di)
    xs, z = xz[..., :di], xz[..., di:]
    prev = state["conv"] if state is not None else None
    xs, conv_state = _depthwise_conv(xs, params["conv_w"], params["conv_b"], prev)
    xs = jax.nn.silu(xs)

    bcdt = xs @ params["w_bcdt"]                         # (B, S, dt_rank+2n)
    dt = jax.nn.softplus(
        (bcdt[..., :dt_rank] @ params["w_dt"]).astype(jnp.float32)
        + params["dt_bias"])                             # (B, S, di)
    b_t = bcdt[..., dt_rank:dt_rank + n].astype(jnp.float32)
    c_t = bcdt[..., dt_rank + n:].astype(jnp.float32)

    a = -jnp.exp(params["a_log"])                        # (di, n)
    decay = jnp.exp(dt[..., None] * a)                   # (B, S, di, n)
    bx = (dt * xs.astype(jnp.float32))[..., None] * b_t[:, :, None, :]
    h0 = (state["ssm"] if state is not None
          else jnp.zeros((B, di, n), jnp.float32))
    if _use_pallas_scan(cfg, S, di):
        y, h_final = _ssm_pallas_cv(decay, bx, c_t, h0, cfg.ssm.chunk_size)
    elif cfg.mamba_fused_y:
        y, h_final = _ssm_scan_chunked_fused_y(decay, bx, c_t, h0,
                                               cfg.ssm.chunk_size)
    else:
        states, h_final = _ssm_scan_chunked(decay, bx, h0, cfg.ssm.chunk_size)
        y = jnp.einsum("bsdn,bsn->bsd", states, c_t)
    y = y + params["d_skip"] * xs.astype(jnp.float32)
    y = (y.astype(x.dtype)) * jax.nn.silu(z)
    out = y @ params["w_out"]
    return out, {"conv": conv_state, "ssm": h_final}


def mamba_decode(params: Params, x: jnp.ndarray, state: Dict[str, jnp.ndarray],
                 cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One-token step.  x: (B, 1, D); state: conv (B, d_conv-1, di), ssm (B, di, n)."""
    out, new_state = mamba_mixer(params, x, cfg, state=state)
    return out, new_state


def state_spec(cfg: ModelConfig, batch: int):
    di, _, n, d_conv = dims(cfg)
    dtype = jnp.dtype(cfg.dtype)
    return {"conv": ((batch, d_conv - 1, di), dtype),
            "ssm": ((batch, di, n), jnp.dtype(jnp.float32))}
