"""Whisper-style encoder-decoder transformer backbone [arXiv:2212.04356].

Per the assignment carve-out, the mel-spectrogram + conv frontend is a STUB:
``input_specs`` supplies precomputed frame embeddings (B, encoder_seq, D).
This module implements everything downstream — the bidirectional audio
encoder, the causal text decoder with cross-attention, and the decode path
whose cache holds both the self-attention ring buffer and the cross-attention
K/V computed once at prefill.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models.attention import decode_attention, flash_attention
from repro.models.layers import (Params, chunked_softmax_xent, dense_init,
                                 embed_init, embed_lookup, init_mlp, mlp,
                                 rms_norm, split_keys)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_xattn(key, cfg: ModelConfig, n_layers: int) -> Params:
    ks = split_keys(key, 4)
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    lead = (n_layers,) if n_layers else ()
    dtype = jnp.dtype(cfg.dtype)
    return {
        "wq": dense_init(ks[0], lead + (D, H * hd), dtype),
        "wk": dense_init(ks[1], lead + (D, KV * hd), dtype),
        "wv": dense_init(ks[2], lead + (D, KV * hd), dtype),
        "wo": dense_init(ks[3], lead + (H * hd, D), dtype),
    }


def init_model(key, cfg: ModelConfig) -> Params:
    ks = split_keys(key, 8)
    dtype = jnp.dtype(cfg.dtype)
    Le, Ld = cfg.encoder_layers, cfg.num_layers
    return {
        "embed": {"w": embed_init(ks[0], (cfg.padded_vocab, cfg.d_model), dtype)},
        "enc_blocks": {
            "attn": attn_lib.init_gqa(ks[1], cfg, Le),
            "mlp": init_mlp(ks[2], cfg.d_model, cfg.d_ff, dtype, Le),
            "ln1": {"w": jnp.ones((Le, cfg.d_model), dtype)},
            "ln2": {"w": jnp.ones((Le, cfg.d_model), dtype)},
        },
        "enc_norm": {"w": jnp.ones((cfg.d_model,), dtype)},
        "dec_blocks": {
            "attn": attn_lib.init_gqa(ks[3], cfg, Ld),
            "xattn": _init_xattn(ks[4], cfg, Ld),
            "mlp": init_mlp(ks[5], cfg.d_model, cfg.d_ff, dtype, Ld),
            "ln1": {"w": jnp.ones((Ld, cfg.d_model), dtype)},
            "lnx": {"w": jnp.ones((Ld, cfg.d_model), dtype)},
            "ln2": {"w": jnp.ones((Ld, cfg.d_model), dtype)},
        },
        "final_norm": {"w": jnp.ones((cfg.d_model,), dtype)},
        "lm_head": {"w": dense_init(ks[6], (cfg.d_model, cfg.padded_vocab),
                                    dtype, scale=0.02)},
    }


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------

def encode(params: Params, frames: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """frames: (B, enc_seq, D) stub embeddings -> encoder hidden states."""
    def body(x, bp):
        h = rms_norm(x, bp["ln1"]["w"], cfg.norm_eps)
        B, S, D = h.shape
        H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = (h @ bp["attn"]["wq"]).reshape(B, S, H, hd).transpose(0, 2, 1, 3)
        k = (h @ bp["attn"]["wk"]).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
        v = (h @ bp["attn"]["wv"]).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
        a = flash_attention(q, k, v, causal=False, chunk=cfg.attn_chunk,
                            cfg=cfg)
        a = a.transpose(0, 2, 1, 3).reshape(B, S, H * hd) @ bp["attn"]["wo"]
        x = x + a
        x = x + mlp(bp["mlp"], rms_norm(x, bp["ln2"]["w"], cfg.norm_eps))
        return x, None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, frames, params["enc_blocks"])
    return rms_norm(x, params["enc_norm"]["w"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# decoder
# ---------------------------------------------------------------------------

def _cross_kv(bp: Params, enc_out: jnp.ndarray, cfg: ModelConfig):
    B, Se, D = enc_out.shape
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    xk = (enc_out @ bp["wk"]).reshape(B, Se, KV, hd)
    xv = (enc_out @ bp["wv"]).reshape(B, Se, KV, hd)
    return xk, xv


def _cross_attend(bp: Params, h, xk, xv, cfg: ModelConfig):
    """h: (B, Sq, D); xk/xv: (B, Se, KV, hd) — bidirectional, no rope."""
    B, Sq, D = h.shape
    H, hd = cfg.num_heads, cfg.head_dim
    q = (h @ bp["wq"]).reshape(B, Sq, H, hd).transpose(0, 2, 1, 3)
    k = xk.transpose(0, 2, 1, 3)
    v = xv.transpose(0, 2, 1, 3)
    a = flash_attention(q, k, v, causal=False, chunk=cfg.attn_chunk, cfg=cfg)
    return a.transpose(0, 2, 1, 3).reshape(B, Sq, H * hd) @ bp["wo"]


def _dec_block(bp: Params, x, enc_out, cfg: ModelConfig, want_cache: bool):
    a, cache = attn_lib.gqa_forward(bp["attn"],
                                    rms_norm(x, bp["ln1"]["w"], cfg.norm_eps), cfg)
    x = x + a
    xk, xv = _cross_kv(bp["xattn"], enc_out, cfg)
    x = x + _cross_attend(bp["xattn"], rms_norm(x, bp["lnx"]["w"], cfg.norm_eps),
                          xk, xv, cfg)
    x = x + mlp(bp["mlp"], rms_norm(x, bp["ln2"]["w"], cfg.norm_eps))
    full_cache = {**cache, "xk": xk, "xv": xv} if want_cache else None
    return x, full_cache


def decode_stack(params: Params, tokens: jnp.ndarray, enc_out: jnp.ndarray,
                 cfg: ModelConfig, want_cache: bool = False):
    x = embed_lookup(params["embed"]["w"], tokens)

    def body(h, bp):
        h, cache = _dec_block(bp, h, enc_out, cfg, want_cache)
        return h, cache

    body_fn = jax.checkpoint(body) if (cfg.remat and not want_cache) else body
    x, caches = jax.lax.scan(body_fn, x, params["dec_blocks"])
    return rms_norm(x, params["final_norm"]["w"], cfg.norm_eps), caches


# ---------------------------------------------------------------------------
# model API
# ---------------------------------------------------------------------------

def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig):
    enc_out = encode(params, batch["frames"], cfg)
    x, _ = decode_stack(params, batch["tokens"], enc_out, cfg)
    xent = chunked_softmax_xent(x, params["lm_head"]["w"], batch["labels"],
                                cfg.logit_chunk, valid_vocab=cfg.vocab_size)
    return xent, {"xent": xent}


def prefill(params: Params, tokens: jnp.ndarray, frames: jnp.ndarray,
            cfg: ModelConfig):
    enc_out = encode(params, frames, cfg)
    x, caches = decode_stack(params, tokens, enc_out, cfg, want_cache=True)
    logits = x[:, -1:] @ params["lm_head"]["w"]
    # self-attn cache: (L, B, S, KV, hd); cross: (L, B, Se, KV, hd)
    return logits, caches


def cache_spec(cfg: ModelConfig, batch: int, cache_len: int) -> Dict[str, Any]:
    L = cfg.num_layers
    dtype = jnp.dtype(cfg.dtype)
    W = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    kv = (L, batch, W, cfg.num_kv_heads, cfg.head_dim)
    xkv = (L, batch, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim)
    return {"k": (kv, dtype), "v": (kv, dtype),
            "xk": (xkv, dtype), "xv": (xkv, dtype)}


def decode_step(params: Params, token: jnp.ndarray, cache, cache_index,
                cfg: ModelConfig):
    """token: (B, 1); cache: stacked {k, v, xk, xv} from prefill/cache_spec."""
    x = embed_lookup(params["embed"]["w"], token)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def body(h, inp):
        bp, bc = inp
        a, new_kv = attn_lib.gqa_decode(
            bp["attn"], rms_norm(h, bp["ln1"]["w"], cfg.norm_eps),
            {"k": bc["k"], "v": bc["v"]}, cache_index, cfg)
        h = h + a
        hq = rms_norm(h, bp["lnx"]["w"], cfg.norm_eps)
        B = hq.shape[0]
        q = (hq @ bp["xattn"]["wq"]).reshape(B, 1, H, hd).transpose(0, 2, 1, 3)
        valid = jnp.ones((B, bc["xk"].shape[1]), bool)
        a = decode_attention(q, bc["xk"].transpose(0, 2, 1, 3),
                             bc["xv"].transpose(0, 2, 1, 3), valid)
        h = h + a.transpose(0, 2, 1, 3).reshape(B, 1, H * hd) @ bp["xattn"]["wo"]
        h = h + mlp(bp["mlp"], rms_norm(h, bp["ln2"]["w"], cfg.norm_eps))
        return h, {**new_kv, "xk": bc["xk"], "xv": bc["xv"]}

    x, new_cache = jax.lax.scan(body, x, (params["dec_blocks"], cache))
    x = rms_norm(x, params["final_norm"]["w"], cfg.norm_eps)
    logits = x @ params["lm_head"]["w"]
    return logits, new_cache
