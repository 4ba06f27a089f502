"""Jamba — hybrid Mamba + attention (1:7 interleave) with MoE every other
layer [arXiv:2403.19887].

The 32-layer stack is 4 *super-blocks* of ``hybrid_block_layers`` (8)
layers.  Layer kinds inside a super-block are heterogeneous (one attention
layer at position ``hybrid_attn_period // 2``, Mamba elsewhere; MoE FFN on
odd positions), so parameters are stored per-position and stacked over the
super-block axis, and ``lax.scan`` runs over super-blocks with the eight
heterogeneous layers unrolled in the body — 60-layer-class models lower to
a compact HLO while keeping the 1:7 mixer pattern exact.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models.layers import (Params, chunked_softmax_xent, dense_init,
                                 embed_init, embed_lookup, init_mlp, mlp,
                                 rms_norm, split_keys)


def block_layout(cfg: ModelConfig):
    """[(mixer, use_moe)] for one super-block (matches core.flops)."""
    out = []
    for i in range(cfg.hybrid_block_layers):
        mixer = "attn" if i == cfg.hybrid_attn_period // 2 else "mamba"
        use_moe = cfg.moe is not None and (i % cfg.moe.every == 1)
        out.append((mixer, use_moe))
    return out


def n_super_blocks(cfg: ModelConfig) -> int:
    return cfg.num_layers // cfg.hybrid_block_layers


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, mixer: str, use_moe: bool, nb: int) -> Params:
    ks = split_keys(key, 2)
    dtype = jnp.dtype(cfg.dtype)
    p: Params = {
        "ln1": {"w": jnp.ones((nb, cfg.d_model), dtype)},
        "ln2": {"w": jnp.ones((nb, cfg.d_model), dtype)},
    }
    if mixer == "attn":
        p["attn"] = attn_lib.init_gqa(ks[0], cfg, nb)
    else:
        p["ssm"] = mamba_lib.init_mamba(ks[0], cfg, nb)
    if use_moe:
        p["moe"] = moe_lib.init_moe(ks[1], cfg, nb)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype, nb)
    return p


def init_model(key, cfg: ModelConfig) -> Params:
    nb = n_super_blocks(cfg)
    layout = block_layout(cfg)
    ks = split_keys(key, len(layout) + 3)
    dtype = jnp.dtype(cfg.dtype)
    blocks = {f"l{i}": _init_layer(ks[i], cfg, m, moe, nb)
              for i, (m, moe) in enumerate(layout)}
    return {
        "embed": {"w": embed_init(ks[-3], (cfg.padded_vocab, cfg.d_model), dtype)},
        "blocks": blocks,
        "final_norm": {"w": jnp.ones((cfg.d_model,), dtype)},
        "lm_head": {"w": dense_init(ks[-2], (cfg.d_model, cfg.padded_vocab),
                                    dtype, scale=0.02)},
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _layer_forward(lp: Params, x, cfg: ModelConfig, mixer: str, use_moe: bool,
                   q_offset: int = 0):
    h = rms_norm(x, lp["ln1"]["w"], cfg.norm_eps)
    if mixer == "attn":
        a, cache = attn_lib.gqa_forward(lp["attn"], h, cfg, q_offset)
    else:
        a, cache = mamba_lib.mamba_mixer(lp["ssm"], h, cfg)
    x = x + a
    h = rms_norm(x, lp["ln2"]["w"], cfg.norm_eps)
    if use_moe:
        m, aux = moe_lib.moe_block(lp["moe"], h, cfg)
    else:
        m, aux = mlp(lp["mlp"], h), {}
    return x + m, aux, cache


def forward(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
            want_cache: bool = False):
    x = embed_lookup(params["embed"]["w"], tokens)
    layout = block_layout(cfg)

    def body(carry, bp):
        h, aux_acc = carry
        caches = {}
        for i, (mixer, use_moe) in enumerate(layout):
            h, aux, cache = _layer_forward(bp[f"l{i}"], h, cfg, mixer, use_moe)
            if aux:
                aux_acc = aux_acc + sum(aux.values())
            if want_cache:
                caches[f"l{i}"] = cache
        return (h, aux_acc), (caches if want_cache else None)

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x, aux), caches = jax.lax.scan(
        body_fn, (x, jnp.zeros((), jnp.float32)), params["blocks"])
    x = rms_norm(x, params["final_norm"]["w"], cfg.norm_eps)
    return x, aux, caches


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig):
    x, aux, _ = forward(params, batch["tokens"], cfg)
    xent = chunked_softmax_xent(x, params["lm_head"]["w"], batch["labels"],
                                cfg.logit_chunk, valid_vocab=cfg.vocab_size)
    return xent + aux, {"xent": xent, "aux": aux}


def prefill(params: Params, tokens: jnp.ndarray, cfg: ModelConfig):
    """Returns last-position logits + decode cache.

    Attention caches from the chunked forward hold full-sequence K/V; Mamba
    caches are the O(1) (conv, ssm) states.
    """
    x, _, caches = forward(params, tokens, cfg, want_cache=True)
    logits = x[:, -1:] @ params["lm_head"]["w"]
    # attn caches come back as (B, S, KV, hd) per super-block laye stacked
    return logits, caches


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def cache_spec(cfg: ModelConfig, batch: int, cache_len: int) -> Dict[str, Any]:
    nb = n_super_blocks(cfg)
    dtype = jnp.dtype(cfg.dtype)
    W = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    spec: Dict[str, Any] = {}
    for i, (mixer, _) in enumerate(block_layout(cfg)):
        if mixer == "attn":
            spec[f"l{i}"] = {
                "k": ((nb, batch, W, cfg.num_kv_heads, cfg.head_dim), dtype),
                "v": ((nb, batch, W, cfg.num_kv_heads, cfg.head_dim), dtype)}
        else:
            s = mamba_lib.state_spec(cfg, batch)
            spec[f"l{i}"] = {k: ((nb,) + v[0], v[1]) for k, v in s.items()}
    return spec


def _layer_decode(lp: Params, x, cache, cache_index, cfg: ModelConfig,
                  mixer: str, use_moe: bool):
    h = rms_norm(x, lp["ln1"]["w"], cfg.norm_eps)
    if mixer == "attn":
        a, new_cache = attn_lib.gqa_decode(lp["attn"], h, cache, cache_index, cfg)
    else:
        a, new_cache = mamba_lib.mamba_decode(lp["ssm"], h, cache, cfg)
    x = x + a
    h = rms_norm(x, lp["ln2"]["w"], cfg.norm_eps)
    if use_moe:
        m, _ = moe_lib.moe_block(lp["moe"], h, cfg)
    else:
        m = mlp(lp["mlp"], h)
    return x + m, new_cache


def decode_step(params: Params, token: jnp.ndarray, cache, cache_index,
                cfg: ModelConfig):
    x = embed_lookup(params["embed"]["w"], token)
    layout = block_layout(cfg)

    def body(h, inp):
        bp, bc = inp
        new_caches = {}
        for i, (mixer, use_moe) in enumerate(layout):
            h, nc = _layer_decode(bp[f"l{i}"], h, bc[f"l{i}"], cache_index,
                                  cfg, mixer, use_moe)
            new_caches[f"l{i}"] = nc
        return h, new_caches

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    x = rms_norm(x, params["final_norm"]["w"], cfg.norm_eps)
    logits = x @ params["lm_head"]["w"]
    return logits, new_cache
