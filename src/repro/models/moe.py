"""Mixture-of-Experts with GShard-style einsum dispatch.

TPU-native choices:
- dense one-hot dispatch/combine einsums (SPMD-friendly; the dispatch tensor
  shards over (data, model) axes, experts shard over the `model` axis),
- per-batch-row groups with a capacity factor (tokens over capacity drop
  through the residual connection),
- router computed in fp32; load-balance + router-z auxiliary losses.

There is no gather/scatter ("sort-based") dispatch and no expert-parallel
all-to-all: the einsum form is the only one.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.layers import Params, dense_init, split_keys


def expert_capacity(moe: MoEConfig, group_tokens: int) -> int:
    cap = int(moe.top_k * group_tokens * moe.capacity_factor / moe.num_experts)
    return max(cap, 1)


def init_moe(key, cfg: ModelConfig, n_layers: int = 0) -> Params:
    moe = cfg.moe
    assert moe is not None
    d_ff = moe.d_ff_expert or cfg.d_ff
    ks = split_keys(key, 5)
    lead = (n_layers,) if n_layers else ()
    dtype = jnp.dtype(cfg.dtype)
    E = moe.num_experts
    p: Params = {
        "router": dense_init(ks[0], lead + (cfg.d_model, E), dtype, scale=0.02),
        "wi": dense_init(ks[1], lead + (E, cfg.d_model, d_ff), dtype),
        "wg": dense_init(ks[2], lead + (E, cfg.d_model, d_ff), dtype),
        "wo": dense_init(ks[3], lead + (E, d_ff, cfg.d_model), dtype),
    }
    if moe.num_shared_experts:
        from repro.models.layers import init_mlp
        p["shared"] = init_mlp(ks[4], cfg.d_model,
                               d_ff * moe.num_shared_experts, dtype,
                               n_layers=n_layers)
    return p


def moe_block(params: Params, x: jnp.ndarray, cfg: ModelConfig
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, S, D) -> (out, aux_losses).

    Groups are batch rows: capacity is computed over S tokens per row.
    """
    moe = cfg.moe
    B, S, D = x.shape
    E, K = moe.num_experts, moe.top_k
    C = expert_capacity(moe, S)
    C = min(C, S)

    logits = (x @ params["router"]).astype(jnp.float32)          # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)                # (B,S,K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # position-in-expert for capacity masking ------------------------------
    # sel: (B,S,K,E) one-hot of chosen experts, ranked by (s, k) priority
    sel = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)
    flat = sel.reshape(B, S * K, E)
    pos = jnp.cumsum(flat, axis=1) - flat                        # tokens ahead
    pos = pos.reshape(B, S, K, E)
    within = pos < C
    sel = sel * within
    pos_idx = jnp.sum(pos * sel, axis=-1).astype(jnp.int32)      # (B,S,K)

    # dispatch/combine tensors (B,S,E,C) -----------------------------------
    pos_oh = jax.nn.one_hot(pos_idx, C, dtype=jnp.float32)       # (B,S,K,C)
    disp = jnp.einsum("bske,bskc->bsec", sel, pos_oh)
    comb = jnp.einsum("bske,bskc,bsk->bsec", sel, pos_oh,
                      gate_vals.astype(jnp.float32))

    dt = x.dtype
    xin = jnp.einsum("bsec,bsd->ebcd", disp.astype(dt), x)       # (E,B,C,D)
    h = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", xin, params["wg"]))
    h = h * jnp.einsum("ebcd,edf->ebcf", xin, params["wi"])
    out_e = jnp.einsum("ebcf,efd->ebcd", h, params["wo"])        # (E,B,C,D)
    out = jnp.einsum("bsec,ebcd->bsd", comb.astype(dt), out_e)

    if moe.num_shared_experts and "shared" in params:
        from repro.models.layers import mlp
        out = out + mlp(params["shared"], x)

    # auxiliary losses ------------------------------------------------------
    # load balance: E * sum_e f_e * p_e  (Switch Transformer eq. 4-6)
    top1 = jax.nn.one_hot(gate_idx[..., 0], E, dtype=jnp.float32)
    f = jnp.mean(top1, axis=(0, 1))
    p = jnp.mean(probs, axis=(0, 1))
    lb = E * jnp.sum(f * p) * moe.load_balance_loss
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * moe.router_z_loss
    aux = {"load_balance": lb, "router_z": z}
    return out, aux
