"""Attention variants: GQA with chunked online-softmax ("flash" in pure jnp,
or JAX's Pallas TPU splash kernel under ``cfg.use_pallas``), MLA (DeepSeek-V2
latent attention), sliding-window masking, and single-token decode against
(optionally ring-buffer) KV caches.

Memory discipline: the kernel keeps its score tiles in VMEM, forward and
backward. The jnp path holds one (attn_chunk, attn_chunk) score tile per
(batch, kv-head, group) when attn_chunk divides the lengths, else the whole
(Sq, Skv) matrix.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro.configs.base import ModelConfig
from repro.models.layers import Params, apply_rope, dense_init, split_keys

NEG_INF = -1e30
# The splash kernel names its residuals (``out``, ``logsumexp``) so that a
# checkpoint policy can keep them (``remat``); elsewhere the name does nothing.
SPLASH_RESIDUALS = "attn.splash_residuals"


# ---------------------------------------------------------------------------
# chunked online-softmax attention core
# ---------------------------------------------------------------------------

def _flash_core(q, k, v, q_pos, kv_pos, *, causal: bool, window: int, chunk: int):
    """q: (B, Hkv, G, Sq, d); k, v: (B, Hkv, Skv, d).

    q_pos: (Sq,) absolute positions of queries; kv_pos: (Skv,).
    Returns (B, Hkv, G, Sq, d).  Scans over KV chunks with a running
    (max, denominator, accumulator) triple; fp32 accumulation.
    """
    B, Hkv, G, Sq, d = q.shape
    dv = v.shape[-1]                                     # may differ from d (MLA)
    Skv = k.shape[2]
    chunk = min(chunk, Skv)
    if Skv % chunk != 0:
        chunk = Skv
    n_blocks = Skv // chunk

    kb = k.reshape(B, Hkv, n_blocks, chunk, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, Hkv, n_blocks, chunk, dv).transpose(2, 0, 1, 3, 4)
    pb = kv_pos.reshape(n_blocks, chunk)

    scale = 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32) * scale

    def body(carry, inp):
        m, l, acc = carry
        kc, vc, pc = inp                                     # (B,Hkv,chunk,d), (chunk,)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kc.astype(jnp.float32))
        mask = jnp.ones((Sq, chunk), bool)
        if causal:
            mask &= pc[None, :] <= q_pos[:, None]
        if window:
            mask &= pc[None, :] > q_pos[:, None] - window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def use_pallas(cfg) -> bool:
    """Kernel dispatch policy: Pallas on TPU (or when forced for tests)."""
    mode = getattr(cfg, "use_pallas", "auto")
    if mode == "always":
        return True
    if mode == "never":
        return False
    return jax.default_backend() == "tpu"


def _tile(n: int, largest: int = 512) -> int:
    """Largest kernel block (1024, 512, 256 or 128), at most ``largest``,
    that divides ``n``."""
    return next(b for b in (1024, 512, 256, 128) if b <= largest and n % b == 0)


def remat(body, cfg):
    """``body`` under ``jax.checkpoint`` when ``cfg.remat``, else ``body``.

    The policy keeps the splash kernel's residuals, so the backward reads the
    ``out`` and ``logsumexp`` the forward wrote and does not run the attention
    forward again; everything else is recomputed (``remat_policy="full"``) or,
    under ``"dots"``, matmul outputs without batch dimensions are kept too. A
    layer with no splash call saves what a plain checkpoint saves.
    """
    if not cfg.remat:
        return body
    cp = jax.checkpoint_policies
    policy = cp.save_only_these_names(SPLASH_RESIDUALS)
    if cfg.remat_policy == "dots":
        policy = cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable, policy)
    return jax.checkpoint(body, policy=policy)


def _flash_pallas(q, k, v, causal):
    """JAX's Pallas TPU splash-attention kernel: forward, and one fused
    backward kernel for dq, dk and dv, with f32 softmax statistics; the
    scores stay in VMEM both ways. Hq may be a multiple of Hkv (GQA).

    Sq and Skv are padded up to multiples of 128 and the output sliced
    back; the slice's gradient is zero on padded rows, so dk, dv get nothing
    from them. A static mask keeps padded keys from every query (causal
    calls, Sq <= Skv, need none). The kv block is the whole padded kv up to
    2048 (the encoder's 1536: 14% less fwd+bwd time on a v5e than 512).
    Past 2048 the q and kv blocks are 1024 where that divides, computed in
    tiles of 512: StableLM's causal 32 x 4096 x 80 took 12% less fwd+bwd
    time on a v5e than with blocks of 512 (5.50 ms against 6.27).
    q, k and v enter the kernel sequence-minor, (d, S): with a head dim of
    64 the (S, d) layout half-fills each 128-lane tile, and whisper-base's
    step on a v5e took 310 ms this way against 355; at 80, (S, d) was 6%
    slower.
    """
    Hq, Sq, d = q.shape[1:]
    Skv = k.shape[2]
    Sqp, Skp = Sq + -Sq % 128, Skv + -Skv % 128
    if causal:
        mask = splash.CausalMask((Sqp, Skp))
    elif Skp != Skv:
        mask = splash.NumpyMask(
            np.broadcast_to(np.arange(Skp) < Skv, (Sqp, Skp)).copy())
    else:
        mask = splash.FullMask((Sqp, Skp))
    long = Skp > 2048
    bq, bkv_compute = _tile(Sqp, 1024 if long else 512), _tile(Skp)
    bkv = _tile(Skp, 1024) if long else Skp
    seq_minor = splash.QKVLayout.SEQ_MINOR
    blocks = splash.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkv_compute,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv_compute,
        use_fused_bwd_kernel=True, q_layout=seq_minor, k_layout=seq_minor,
        v_layout=seq_minor)
    kernel = splash.make_splash_mha(
        splash.MultiHeadMask([mask] * Hq), block_sizes=blocks, head_shards=1,
        q_seq_shards=1, residual_checkpoint_name=SPLASH_RESIDUALS,
        interpret=jax.default_backend() != "tpu")
    pad = lambda x, n: jnp.pad(x, ((0, 0), (0, 0), (0, n - x.shape[2]), (0, 0)))
    q = (q * (1.0 / math.sqrt(d))).astype(q.dtype)   # the kernel does not scale
    out = jax.vmap(kernel)(pad(q, Sqp), pad(k, Skp), pad(v, Skp))
    return out[:, :, :Sq]


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    chunk: int = 1024, q_offset: int = 0,
                    cfg=None) -> jnp.ndarray:
    """GQA-aware chunked attention.

    q: (B, Hq, Sq, d); k, v: (B, Hkv, Skv, d); Hq % Hkv == 0.
    ``q_offset`` shifts query positions (prefill continuation).
    Queries are processed in blocks of ``chunk`` via lax.map so prefill_32k
    never holds more than one (chunk x chunk) score tile per head-group.

    When ``cfg.use_pallas`` resolves true and the shape qualifies (no
    window/offset, same qk/v dims), dispatches to JAX's Pallas TPU
    splash-attention kernel, forward and backward, at any length: both
    lengths are padded to multiples of 128 and the padded keys masked
    (``_flash_pallas``).
    """
    if (cfg is not None and use_pallas(cfg) and window == 0 and q_offset == 0
            and q.shape[-1] == v.shape[-1]):
        return _flash_pallas(q, k, v, causal)
    B, Hq, Sq, d = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, Sq, d)
    kv_pos = jnp.arange(k.shape[2])

    qchunk = min(chunk, Sq)
    if Sq % qchunk != 0:
        qchunk = Sq
    nq = Sq // qchunk
    if nq == 1:
        q_pos = q_offset + jnp.arange(Sq)
        out = _flash_core(qg, k, v, q_pos, kv_pos, causal=causal,
                          window=window, chunk=chunk)
    else:
        qb = qg.reshape(B, Hkv, G, nq, qchunk, d).transpose(3, 0, 1, 2, 4, 5)

        def one(args):
            qc, i = args
            q_pos = q_offset + i * qchunk + jnp.arange(qchunk)
            return _flash_core(qc, k, v, q_pos, kv_pos, causal=causal,
                               window=window, chunk=chunk)

        outs = jax.lax.map(one, (qb, jnp.arange(nq)))
        out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(B, Hkv, G, Sq, v.shape[-1])
    return out.reshape(B, Hq, Sq, v.shape[-1])


def decode_attention(q, k, v, valid_mask) -> jnp.ndarray:
    """Single-token attention.  q: (B, Hq, 1, d); k, v: (B, Hkv, S, d);
    valid_mask: (B, S) bool (ring-buffer slots that hold real tokens)."""
    B, Hq, _, d = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, d).astype(jnp.float32) / math.sqrt(d)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k.astype(jnp.float32))
    s = jnp.where(valid_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, 1, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def init_gqa(key, cfg: ModelConfig, n_layers: int = 0) -> Params:
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


def gqa_forward(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                q_offset: int = 0) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Training / prefill path.  x: (B, S, D) -> (out, cache)."""
    B, S, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    k = (x @ params["wk"]).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
    v = (x @ params["wv"]).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
    pos = q_offset + jnp.arange(S)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    out = flash_attention(q, k, v, causal=True, window=cfg.sliding_window,
                          chunk=cfg.attn_chunk, q_offset=q_offset, cfg=cfg)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    cache = {"k": k.transpose(0, 2, 1, 3), "v": v.transpose(0, 2, 1, 3)}  # (B,S,KV,hd)
    return out @ params["wo"], cache


def gqa_decode(params: Params, x: jnp.ndarray, cache: Dict[str, jnp.ndarray],
               cache_index: jnp.ndarray, cfg: ModelConfig
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One-token decode.  x: (B, 1, D); cache k/v: (B, W, KV, hd) ring buffer
    (W = sliding window if set, else max seq); cache_index: () int32 count of
    tokens already written."""
    B, _, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    W = cache["k"].shape[1]
    q = (x @ params["wq"]).reshape(B, 1, H, hd).transpose(0, 2, 1, 3)
    k = (x @ params["wk"]).reshape(B, 1, KV, hd)
    v = (x @ params["wv"]).reshape(B, 1, KV, hd)
    pos = cache_index[None]                       # absolute position of new token
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k.transpose(0, 2, 1, 3), pos, cfg.rope_theta).transpose(0, 2, 1, 3)
    slot = jnp.mod(cache_index, W)
    new_k = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    n_valid = jnp.minimum(cache_index + 1, W)
    valid = (jnp.arange(W)[None, :] < n_valid) | jnp.zeros((B, 1), bool)
    out = decode_attention(q, new_k.transpose(0, 2, 1, 3),
                           new_v.transpose(0, 2, 1, 3), valid)
    out = out.transpose(0, 2, 1, 3).reshape(B, 1, H * hd)
    return out @ params["wo"], {"k": new_k, "v": new_v}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, n_layers: int = 0) -> Params:
    ks = split_keys(key, 7)
    D, H, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    L, R = cfg.mla_kv_lora, cfg.mla_rope_dim
    lead = (n_layers,) if n_layers else ()
    dtype = jnp.dtype(cfg.dtype)
    return {
        "w_dkv": dense_init(ks[0], lead + (D, L), dtype),      # down-proj to latent
        "w_kr": dense_init(ks[1], lead + (D, R), dtype),       # shared rope key
        "w_uk": dense_init(ks[2], lead + (L, H * hd), dtype),  # up-proj keys
        "w_uv": dense_init(ks[3], lead + (L, H * hd), dtype),  # up-proj values
        "w_q": dense_init(ks[4], lead + (D, H * (hd + R)), dtype),
        "w_o": dense_init(ks[5], lead + (H * hd, D), dtype),
        "ln_kv": jnp.ones(lead + (L,), dtype),
    }


def _mla_qkv(params, x, cfg, pos):
    """Shared projection logic.  Returns q_nope,(B,H,S,hd) q_rope,(B,H,S,R)
    latent c_kv (B,S,L), k_rope (B,S,R)."""
    from repro.models.layers import rms_norm
    B, S, D = x.shape
    H, hd, R = cfg.num_heads, cfg.head_dim, cfg.mla_rope_dim
    q = (x @ params["w_q"]).reshape(B, S, H, hd + R).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
    c_kv = rms_norm(x @ params["w_dkv"], params["ln_kv"], cfg.norm_eps)
    k_rope = apply_rope((x @ params["w_kr"])[:, None], pos, cfg.rope_theta)[:, 0]
    return q_nope, q_rope, c_kv, k_rope


def mla_forward(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                q_offset: int = 0) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    B, S, D = x.shape
    H, hd, R = cfg.num_heads, cfg.head_dim, cfg.mla_rope_dim
    pos = q_offset + jnp.arange(S)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, x, cfg, pos)
    k_nope = (c_kv @ params["w_uk"]).reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    v = (c_kv @ params["w_uv"]).reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    # fold the shared rope-key into every head by concatenation
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (B, H, S, R))], axis=-1)
    out = flash_attention(q_full, k_full, v, causal=True,
                          window=cfg.sliding_window, chunk=cfg.attn_chunk,
                          q_offset=q_offset)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    return out @ params["w_o"], {"c_kv": c_kv, "k_rope": k_rope}


def mla_decode(params: Params, x: jnp.ndarray, cache: Dict[str, jnp.ndarray],
               cache_index: jnp.ndarray, cfg: ModelConfig
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Decode with the latent cache: c_kv (B, W, L), k_rope (B, W, R)."""
    B, _, D = x.shape
    H, hd, R = cfg.num_heads, cfg.head_dim, cfg.mla_rope_dim
    W = cache["c_kv"].shape[1]
    pos = cache_index[None]
    q_nope, q_rope, c_new, kr_new = _mla_qkv(params, x, cfg, pos)
    slot = jnp.mod(cache_index, W)
    c_kv = jax.lax.dynamic_update_slice(cache["c_kv"], c_new, (0, slot, 0))
    k_rope = jax.lax.dynamic_update_slice(cache["k_rope"], kr_new, (0, slot, 0))
    n_valid = jnp.minimum(cache_index + 1, W)
    valid = jnp.arange(W)[None, :] < n_valid                      # (1, W)
    # score via the latent space: q_nope projected back through w_uk
    # (B,H,1,hd) x (L,H*hd) -> absorb: q_lat (B,H,L)
    w_uk = params["w_uk"].reshape(-1, H, hd)
    q_lat = jnp.einsum("bhd,lhd->bhl", q_nope[:, :, 0], w_uk)
    s = jnp.einsum("bhl,bwl->bhw", q_lat.astype(jnp.float32),
                   c_kv.astype(jnp.float32))
    s += jnp.einsum("bhr,bwr->bhw", q_rope[:, :, 0].astype(jnp.float32),
                    k_rope.astype(jnp.float32))
    s = s / math.sqrt(hd + R)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhw,bwl->bhl", p, c_kv.astype(jnp.float32))  # latent ctx
    w_uv = params["w_uv"].reshape(-1, H, hd)
    out = jnp.einsum("bhl,lhd->bhd", ctx, w_uv.astype(jnp.float32))
    out = out.reshape(B, 1, H * hd).astype(x.dtype)
    return out @ params["w_o"], {"c_kv": c_kv, "k_rope": k_rope}
