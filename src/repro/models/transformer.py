"""Decoder-only transformer stack covering the dense and MoE families.

Layers are stacked along a leading axis and driven by ``jax.lax.scan`` so
that 60-layer configs lower to a compact HLO.  The same stack is reused by
the VLM wrapper (prefix embeddings) and — with its own mixers — by the
hybrid/SSM stacks.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models.layers import (Params, chunked_softmax_xent, dense_init,
                                 embed_init, embed_lookup, init_mlp, mlp,
                                 rms_norm, split_keys)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: ModelConfig, n_layers: int):
    if cfg.attention == "mla":
        return attn_lib.init_mla(key, cfg, n_layers)
    return attn_lib.init_gqa(key, cfg, n_layers)


def _init_block_stack(key, cfg: ModelConfig, n_layers: int, use_moe: bool) -> Params:
    ks = split_keys(key, 4)
    dtype = jnp.dtype(cfg.dtype)
    lead = (n_layers,) if n_layers else ()
    p = {
        "attn": _init_attn(ks[0], cfg, n_layers),
        "ln1": {"w": jnp.ones(lead + (cfg.d_model,), dtype)},
        "ln2": {"w": jnp.ones(lead + (cfg.d_model,), dtype)},
    }
    if use_moe:
        p["moe"] = moe_lib.init_moe(ks[1], cfg, n_layers)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype, n_layers)
    return p


def init_decoder(key, cfg: ModelConfig) -> Params:
    ks = split_keys(key, 5)
    dtype = jnp.dtype(cfg.dtype)
    n_dense_first = cfg.moe.first_dense if cfg.moe else 0
    n_scan = cfg.num_layers - n_dense_first
    params: Params = {
        "embed": {"w": embed_init(ks[0], (cfg.padded_vocab, cfg.d_model), dtype)},
        "final_norm": {"w": jnp.ones((cfg.d_model,), dtype)},
        "blocks": _init_block_stack(ks[1], cfg, n_scan, use_moe=cfg.moe is not None),
    }
    if n_dense_first:
        params["first_blocks"] = _init_block_stack(ks[2], cfg, n_dense_first,
                                                   use_moe=False)
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": dense_init(ks[3], (cfg.d_model, cfg.padded_vocab),
                                             dtype, scale=0.02)}
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _block_forward(bp: Params, x, cfg: ModelConfig, use_moe: bool, q_offset: int = 0):
    """One transformer layer.  Returns (x, aux, cache)."""
    if cfg.attention == "mla":
        a, cache = attn_lib.mla_forward(bp["attn"], rms_norm(x, bp["ln1"]["w"], cfg.norm_eps),
                                        cfg, q_offset)
    else:
        a, cache = attn_lib.gqa_forward(bp["attn"], rms_norm(x, bp["ln1"]["w"], cfg.norm_eps),
                                        cfg, q_offset)
    x = x + a
    h = rms_norm(x, bp["ln2"]["w"], cfg.norm_eps)
    if use_moe:
        m, aux = moe_lib.moe_block(bp["moe"], h, cfg)
    else:
        m, aux = mlp(bp["mlp"], h), {}
    return x + m, aux, cache


def forward(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
            prefix_embeds: Optional[jnp.ndarray] = None, want_cache: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
    """tokens: (B, S) int32 -> (hidden (B,S,D), aux_loss scalar, caches)."""
    x = embed_lookup(params["embed"]["w"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    aux_total = jnp.zeros((), jnp.float32)
    caches = {}

    def run_stack(x, stack, n_layers, use_moe, name):
        nonlocal aux_total, caches

        def body(carry, lp):
            h, aux_acc = carry
            h, aux, cache = _block_forward(lp, h, cfg, use_moe)
            aux_acc = aux_acc + sum(aux.values()) if aux else aux_acc
            out = cache if want_cache else None
            return (h, aux_acc), out

        body_fn = attn_lib.remat(body, cfg)
        if cfg.scan_layers and n_layers > 1:
            (x, aux_acc), cache = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)), stack)
        else:
            aux_acc = jnp.zeros((), jnp.float32)
            cache_list = []
            for i in range(n_layers):
                lp = jax.tree_util.tree_map(lambda p: p[i], stack) if n_layers > 1 else (
                    jax.tree_util.tree_map(lambda p: p[0], stack))
                (x, aux_acc), c = body_fn((x, aux_acc), lp)
                cache_list.append(c)
            cache = (jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *cache_list)
                     if want_cache else None)
        aux_total = aux_total + aux_acc
        if want_cache:
            caches[name] = cache
        return x

    if "first_blocks" in params:
        n_first = cfg.moe.first_dense
        x = run_stack(x, params["first_blocks"], n_first, False, "first_blocks")
    n_scan = cfg.num_layers - (cfg.moe.first_dense if cfg.moe else 0)
    x = run_stack(x, params["blocks"], n_scan, cfg.moe is not None, "blocks")
    x = rms_norm(x, params["final_norm"]["w"], cfg.norm_eps)
    return x, aux_total, caches


def lm_head_weight(params: Params, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return params["embed"]["w"].T
    return params["lm_head"]["w"]


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    prefix = batch.get("prefix_embeds")
    x, aux, _ = forward(params, batch["tokens"], cfg, prefix_embeds=prefix)
    if prefix is not None:
        x = x[:, prefix.shape[1]:]
    xent = chunked_softmax_xent(x, lm_head_weight(params, cfg),
                                batch["labels"], cfg.logit_chunk,
                                valid_vocab=cfg.vocab_size)
    return xent + aux, {"xent": xent, "aux": aux}


def prefill(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
            prefix_embeds: Optional[jnp.ndarray] = None):
    """Prefill: hidden states of the final position -> next-token logits,
    plus per-layer KV caches."""
    x, _, caches = forward(params, tokens, cfg, prefix_embeds=prefix_embeds,
                           want_cache=True)
    logits = x[:, -1:] @ lm_head_weight(params, cfg)
    return logits, caches


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def cache_spec(cfg: ModelConfig, batch: int, cache_len: int) -> Dict[str, Any]:
    """Shapes of the decode cache (ring buffer of ``cache_len`` slots)."""
    W = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    dtype = jnp.dtype(cfg.dtype)
    n_first = cfg.moe.first_dense if cfg.moe else 0
    n_scan = cfg.num_layers - n_first

    def layer_cache(n):
        if cfg.attention == "mla":
            return {"c_kv": ((n, batch, W, cfg.mla_kv_lora), dtype),
                    "k_rope": ((n, batch, W, cfg.mla_rope_dim), dtype)}
        return {"k": ((n, batch, W, cfg.num_kv_heads, cfg.head_dim), dtype),
                "v": ((n, batch, W, cfg.num_kv_heads, cfg.head_dim), dtype)}

    spec = {"blocks": layer_cache(n_scan)}
    if n_first:
        spec["first_blocks"] = layer_cache(n_first)
    return spec


def init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> Dict[str, Any]:
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s[0], s[1]), cache_spec(cfg, batch, cache_len),
        is_leaf=lambda s: isinstance(s, tuple))


def _block_decode(bp: Params, x, cache, cache_index, cfg: ModelConfig, use_moe: bool):
    if cfg.attention == "mla":
        a, new_cache = attn_lib.mla_decode(bp["attn"],
                                           rms_norm(x, bp["ln1"]["w"], cfg.norm_eps),
                                           cache, cache_index, cfg)
    else:
        a, new_cache = attn_lib.gqa_decode(bp["attn"],
                                           rms_norm(x, bp["ln1"]["w"], cfg.norm_eps),
                                           cache, cache_index, cfg)
    x = x + a
    h = rms_norm(x, bp["ln2"]["w"], cfg.norm_eps)
    if use_moe:
        m, _ = moe_lib.moe_block(bp["moe"], h, cfg)
    else:
        m = mlp(bp["mlp"], h)
    return x + m, new_cache


def decode_step(params: Params, token: jnp.ndarray, cache: Dict[str, Any],
                cache_index: jnp.ndarray, cfg: ModelConfig
                ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """token: (B, 1) int32; cache_index: () int32 tokens already cached.

    Returns (logits (B, 1, V), new_cache)."""
    x = embed_lookup(params["embed"]["w"], token)
    new_caches = {}

    def run_stack(x, stack, stack_cache, n_layers, use_moe, name):
        def body(h, inp):
            lp, lc = inp
            h, nc = _block_decode(lp, h, lc, cache_index, cfg, use_moe)
            return h, nc

        if cfg.scan_layers and n_layers > 1:
            x, nc = jax.lax.scan(body, x, (stack, stack_cache))
        else:
            ncs = []
            for i in range(n_layers):
                lp = jax.tree_util.tree_map(lambda p: p[i] if n_layers > 1 else p[0], stack)
                lc = jax.tree_util.tree_map(lambda p: p[i] if n_layers > 1 else p[0], stack_cache)
                x, c = body(x, (lp, lc))
                ncs.append(c)
            nc = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ncs)
        new_caches[name] = nc
        return x

    if "first_blocks" in params:
        n_first = cfg.moe.first_dense
        x = run_stack(x, params["first_blocks"], cache["first_blocks"],
                      n_first, False, "first_blocks")
    n_scan = cfg.num_layers - (cfg.moe.first_dense if cfg.moe else 0)
    x = run_stack(x, params["blocks"], cache["blocks"], n_scan,
                  cfg.moe is not None, "blocks")
    x = rms_norm(x, params["final_norm"]["w"], cfg.norm_eps)
    logits = x @ lm_head_weight(params, cfg)
    return logits, new_caches
