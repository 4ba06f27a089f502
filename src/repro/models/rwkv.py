"""RWKV-6 "Finch" — attention-free time-mix with data-dependent decay.

The WKV recurrence per head (state S in R^{hd x hd}):

    y_t = r_t @ (S_{t-1} + (u * k_t) v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T          w_t = exp(-exp(w0 + lora(x)))

TPU-native chunked form: a ``lax.scan`` over chunks carries S; inside a
chunk the pairwise decay tensor ``exp(Lx[t]-L[j])`` (always <= 1, so fp32
underflow is the *correct* limit — no logspace ratio explosions) gives an
intra-chunk "decay-weighted attention" einsum that maps onto the MXU.

Token-shift is the static-mix variant (the data-dependent *decay* — the
Finch headline feature — is kept; the dynamic token-shift LoRA is
simplified to learned static interpolation, noted in DESIGN.md).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import remat
from repro.models.layers import (Params, chunked_softmax_xent, dense_init,
                                 embed_init, embed_lookup, rms_norm,
                                 split_keys)

DECAY_LORA = 64


def head_dims(cfg: ModelConfig) -> Tuple[int, int]:
    hd = cfg.ssm.head_dim
    return cfg.d_model // hd, hd


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_time_mix(key, cfg: ModelConfig, n_layers: int = 0) -> Params:
    D = cfg.d_model
    ks = split_keys(key, 7)
    lead = (n_layers,) if n_layers else ()
    dtype = jnp.dtype(cfg.dtype)
    # per-channel decay-speed init (RWKV convention): slow channels keep
    # long-range state, fast channels decay within a few tokens
    ratio = jnp.arange(D, dtype=jnp.float32) / max(D - 1, 1)
    w0 = -6.0 + 5.0 * ratio ** 0.7
    return {
        "w_r": dense_init(ks[0], lead + (D, D), dtype),
        "w_k": dense_init(ks[1], lead + (D, D), dtype),
        "w_v": dense_init(ks[2], lead + (D, D), dtype),
        "w_g": dense_init(ks[3], lead + (D, D), dtype),
        "w_o": dense_init(ks[4], lead + (D, D), dtype),
        "w_decay": w0 * jnp.ones(lead + (D,), jnp.float32),
        "w_decay_lora_a": dense_init(ks[5], lead + (D, DECAY_LORA), dtype, scale=0.01),
        "w_decay_lora_b": dense_init(ks[6], lead + (DECAY_LORA, D), dtype, scale=0.01),
        "u_bonus": jnp.zeros(lead + (D,), jnp.float32),
        "mix": 0.5 * jnp.ones(lead + (5, D), jnp.float32),   # r,k,v,w,g
        "ln_x": jnp.ones(lead + (D,), dtype),                # per-head group norm
    }


def init_channel_mix(key, cfg: ModelConfig, n_layers: int = 0) -> Params:
    D, F = cfg.d_model, cfg.d_ff
    ks = split_keys(key, 3)
    lead = (n_layers,) if n_layers else ()
    dtype = jnp.dtype(cfg.dtype)
    return {
        "wr_ch": dense_init(ks[0], lead + (D, D), dtype),
        "wk_ch": dense_init(ks[1], lead + (D, F), dtype),
        "wv_ch": dense_init(ks[2], lead + (F, D), dtype),
        "mix_ch": 0.5 * jnp.ones(lead + (2, D), jnp.float32),  # r,k
    }


def init_model(key, cfg: ModelConfig) -> Params:
    ks = split_keys(key, 5)
    dtype = jnp.dtype(cfg.dtype)
    L = cfg.num_layers
    return {
        "embed": {"w": embed_init(ks[0], (cfg.padded_vocab, cfg.d_model), dtype)},
        "blocks": {
            "ln1": {"w": jnp.ones((L, cfg.d_model), dtype)},
            "ln2": {"w": jnp.ones((L, cfg.d_model), dtype)},
            "rwkv": init_time_mix(ks[1], cfg, L),
            "cmix": init_channel_mix(ks[2], cfg, L),
        },
        "final_norm": {"w": jnp.ones((cfg.d_model,), dtype)},
        "lm_head": {"w": dense_init(ks[3], (cfg.d_model, cfg.padded_vocab),
                                    dtype, scale=0.02)},
    }


# ---------------------------------------------------------------------------
# WKV chunked kernel (pure jnp reference; Pallas version in repro.kernels.wkv)
# ---------------------------------------------------------------------------

def wkv_chunked(r, k, v, logw, u, s0, chunk: int):
    """r,k,v,logw: (B, S, H, hd) fp32 (logw <= 0); u: (H, hd);
    s0: (B, H, hd, hd).  Returns (y (B,S,H,hd), s_final)."""
    B, S, H, hd = r.shape
    if S % chunk != 0:
        chunk = S
    nc = S // chunk
    rc = r.reshape(B, nc, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    kc = k.reshape(B, nc, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nc, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    wc = logw.reshape(B, nc, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)          # j < t

    def step(s, inp):
        rr, kk, vv, ww = inp                         # (B, C, H, hd)
        L = jnp.cumsum(ww, axis=1)                   # inclusive
        Lx = L - ww                                  # exclusive
        # pairwise decay exp(Lx[t]-L[j]) <= 1 for j < t  (B,H,t,j,hd)
        dec = jnp.exp(jnp.clip(
            Lx.transpose(0, 2, 1, 3)[:, :, :, None, :]
            - L.transpose(0, 2, 1, 3)[:, :, None, :, :], -60.0, 0.0))
        scores = jnp.einsum("bthd,bjhd,bhtjd->bhtj",
                            rr, kk, dec, optimize=True)
        scores = scores * tri[None, None]
        diag = jnp.einsum("bthd,hd,bthd->bth", rr, u, kk)
        y = jnp.einsum("bhtj,bjhd->bthd", scores, vv)
        y += diag[..., None] * vv
        # carried-state contribution and state update
        y += jnp.einsum("bthd,bhde->bthe", rr * jnp.exp(Lx), s)
        k_dec = kk * jnp.exp(L[:, -1:] - L)          # exp <= 1
        s_new = s * jnp.exp(L[:, -1])[..., None] \
            + jnp.einsum("bjhd,bjhe->bhde", k_dec, vv)
        return s_new, y

    s_final, yc = jax.lax.scan(step, s0, (rc, kc, vc, wc))
    y = yc.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)
    return y, s_final


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _wkv_pallas_cv(r, k, v, logw, u, s0, chunk):
    """Pallas WKV forward with the chunked-jnp path's gradients."""
    from repro.kernels.wkv import wkv_pallas
    tr = lambda x: x.transpose(0, 2, 1, 3)
    y, s_f = wkv_pallas(tr(r), tr(k), tr(v), tr(logw), u, s0, chunk=chunk,
                        interpret=jax.default_backend() != "tpu")
    return tr(y), s_f


def _wkv_cv_fwd(r, k, v, logw, u, s0, chunk):
    return _wkv_pallas_cv(r, k, v, logw, u, s0, chunk), (r, k, v, logw, u, s0)


def _wkv_cv_bwd(chunk, res, g):
    r, k, v, logw, u, s0 = res
    _, vjp = jax.vjp(
        lambda *a: wkv_chunked(*a, chunk), r, k, v, logw, u, s0)
    return vjp(g)


_wkv_pallas_cv.defvjp(_wkv_cv_fwd, _wkv_cv_bwd)


def _token_shift(x, prev):
    """x: (B, S, D); prev: (B, D) last token of the previous segment."""
    return jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)


def _decay(params, xw):
    lora = jnp.tanh(xw @ params["w_decay_lora_a"]) @ params["w_decay_lora_b"]
    return -jnp.exp(params["w_decay"] + lora.astype(jnp.float32))  # logw <= 0


def _group_norm(y, weight, H, eps=1e-5):
    """Per-head RMS norm over hd; y: (B, S, H, hd) fp32."""
    B, S, _, hd = y.shape
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + eps)
    return y.reshape(B, S, H * hd) * weight


def time_mix(params: Params, x: jnp.ndarray, cfg: ModelConfig,
             state: Dict[str, jnp.ndarray] | None = None):
    """x: (B, S, D) -> (out, {state, tm_x})."""
    B, S, D = x.shape
    H, hd = head_dims(cfg)
    prev = state["tm_x"] if state is not None else jnp.zeros((B, D), x.dtype)
    xs = _token_shift(x, prev)
    mu = params["mix"].astype(x.dtype)                  # (5, D)
    mr, mk, mv, mw, mg = (x + mu[i] * (xs - x) for i in range(5))
    r = (mr @ params["w_r"]).reshape(B, S, H, hd).astype(jnp.float32)
    k = (mk @ params["w_k"]).reshape(B, S, H, hd).astype(jnp.float32)
    v = (mv @ params["w_v"]).reshape(B, S, H, hd).astype(jnp.float32)
    g = jax.nn.silu(mg @ params["w_g"])
    logw = _decay(params, mw).reshape(B, S, H, hd)
    u = params["u_bonus"].reshape(H, hd)
    s0 = (state["state"] if state is not None
          else jnp.zeros((B, H, hd, hd), jnp.float32))
    from repro.models.attention import use_pallas
    if use_pallas(cfg) and S > 1 and S % cfg.ssm.chunk_size == 0:
        y, s_final = _wkv_pallas_cv(r, k, v, logw, u, s0, cfg.ssm.chunk_size)
    else:
        y, s_final = wkv_chunked(r, k, v, logw, u, s0, cfg.ssm.chunk_size)
    y = _group_norm(y, params["ln_x"].astype(jnp.float32), H)
    out = (y.astype(x.dtype) * g) @ params["w_o"]
    return out, {"state": s_final, "tm_x": x[:, -1]}


def channel_mix(params: Params, x: jnp.ndarray,
                state: Dict[str, jnp.ndarray] | None = None):
    B, S, D = x.shape
    prev = state["cm_x"] if state is not None else jnp.zeros((B, D), x.dtype)
    xs = _token_shift(x, prev)
    mu = params["mix_ch"].astype(x.dtype)
    mr, mk = (x + mu[i] * (xs - x) for i in range(2))
    r = jax.nn.sigmoid(mr @ params["wr_ch"])
    kk = jnp.square(jax.nn.relu(mk @ params["wk_ch"]))
    return r * (kk @ params["wv_ch"]), {"cm_x": x[:, -1]}


def _block(bp: Params, x, cfg: ModelConfig, state=None):
    tm_state = ({"state": state["state"], "tm_x": state["tm_x"]}
                if state is not None else None)
    a, tm_new = time_mix(bp["rwkv"], rms_norm(x, bp["ln1"]["w"], cfg.norm_eps),
                         cfg, tm_state)
    x = x + a
    cm_state = {"cm_x": state["cm_x"]} if state is not None else None
    c, cm_new = channel_mix(bp["cmix"], rms_norm(x, bp["ln2"]["w"], cfg.norm_eps),
                            cm_state)
    return x + c, {**tm_new, **cm_new}


# ---------------------------------------------------------------------------
# model API
# ---------------------------------------------------------------------------

def forward(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
            want_state: bool = False, state=None):
    x = embed_lookup(params["embed"]["w"], tokens)

    def body(carry, inp):
        h = carry
        lp, lst = inp
        h, new_state = _block(lp, h, cfg, lst)
        return h, (new_state if want_state else None)

    body_fn = remat(body, cfg)
    if state is None:
        B = tokens.shape[0]
        H, hd = head_dims(cfg)
        L, D = cfg.num_layers, cfg.d_model
        state = {"state": jnp.zeros((L, B, H, hd, hd), jnp.float32),
                 "tm_x": jnp.zeros((L, B, D), x.dtype),
                 "cm_x": jnp.zeros((L, B, D), x.dtype)}
    x, new_state = jax.lax.scan(body_fn, x, (params["blocks"], state))
    x = rms_norm(x, params["final_norm"]["w"], cfg.norm_eps)
    return x, new_state


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig):
    x, _ = forward(params, batch["tokens"], cfg)
    xent = chunked_softmax_xent(x, params["lm_head"]["w"], batch["labels"],
                                cfg.logit_chunk, valid_vocab=cfg.vocab_size)
    return xent, {"xent": xent}


def prefill(params: Params, tokens: jnp.ndarray, cfg: ModelConfig):
    x, state = forward(params, tokens, cfg, want_state=True)
    logits = x[:, -1:] @ params["lm_head"]["w"]
    return logits, state


def decode_step(params: Params, token: jnp.ndarray, cache, cache_index,
                cfg: ModelConfig):
    """token: (B, 1).  The recurrent state is O(1) in sequence length —
    cache_index is unused (kept for API uniformity)."""
    x, new_state = forward(params, token, cfg, want_state=True, state=cache)
    logits = x[:, -1:] @ params["lm_head"]["w"]
    return logits, new_state


def cache_spec(cfg: ModelConfig, batch: int):
    H, hd = head_dims(cfg)
    L, D = cfg.num_layers, cfg.d_model
    dtype = jnp.dtype(cfg.dtype)
    return {"state": ((L, batch, H, hd, hd), jnp.dtype(jnp.float32)),
            "tm_x": ((L, batch, D), dtype),
            "cm_x": ((L, batch, D), dtype)}
