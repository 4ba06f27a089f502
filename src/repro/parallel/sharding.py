"""Sharding rules: map every parameter / activation / cache leaf to a
PartitionSpec for a (pod, data, model) mesh.

Strategies
----------
``dp_tp``   batch over (pod, data); tensor-parallel over `model`;
            params replicated across `data`.
``fsdp_tp`` as above, plus parameters sharded over `data` *within* a pod
            (hybrid FSDP: replicated across pods so param all-gathers stay
            on ICI, gradients cross DCN once).

A rule table only: the trainer (``repro.launch.train``) runs on a 1-D
``data`` mesh with parameters and optimizer state replicated, and applies
none of these specs yet.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def batch_shardable(mesh: Mesh, batch: int) -> Optional[Tuple[str, ...]]:
    axes = batch_axes(mesh)
    size = 1
    for a in axes:
        size *= mesh_axis_size(mesh, a)
    if batch % size == 0:
        return axes
    if batch % mesh_axis_size(mesh, "data") == 0:
        return ("data",)
    return None


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

# trailing-dims rules: (parent, leaf) -> tuple of axis roles
# roles: "f" = fsdp axis (data when fsdp_tp else None), "m" = model, None = repl.
_RULES: Dict[Tuple[str, str], Tuple[Optional[str], ...]] = {
    ("attn", "wq"): ("f", "m"),
    ("attn", "wk"): ("f", "m"),
    ("attn", "wv"): ("f", "m"),
    ("attn", "wo"): ("m", "f"),
    ("attn", "w_q"): ("f", "m"),
    ("attn", "w_uk"): ("f", "m"),
    ("attn", "w_uv"): ("f", "m"),
    ("attn", "w_o"): ("m", "f"),
    ("attn", "w_dkv"): ("f", None),
    ("attn", "w_kr"): ("f", None),
    ("attn", "ln_kv"): (None,),
    ("xattn", "wq"): ("f", "m"),
    ("xattn", "wk"): ("f", "m"),
    ("xattn", "wv"): ("f", "m"),
    ("xattn", "wo"): ("m", "f"),
    ("mlp", "wi"): ("f", "m"),
    ("mlp", "wg"): ("f", "m"),
    ("mlp", "wo"): ("m", "f"),
    ("shared", "wi"): ("f", "m"),
    ("shared", "wg"): ("f", "m"),
    ("shared", "wo"): ("m", "f"),
    ("moe", "router"): ("f", None),
    ("moe", "wi"): ("m", "f", None),
    ("moe", "wg"): ("m", "f", None),
    ("moe", "wo"): ("m", None, "f"),
    ("embed", "w"): ("f", "m"),
    ("lm_head", "w"): ("f", "m"),
    # SSM (mamba) ----------------------------------------------------------
    ("ssm", "w_in"): ("f", "m"),        # (D, 2*d_inner)
    ("ssm", "w_x"): ("f", "m"),         # conv/in projections on d_inner
    ("ssm", "conv_w"): (None, "m"),     # (d_conv, d_inner)
    ("ssm", "conv_b"): ("m",),
    ("ssm", "w_bcdt"): ("m", None),     # (d_inner, 2*d_state+dt_rank)
    ("ssm", "w_dt"): (None, "m"),       # (dt_rank, d_inner)
    ("ssm", "dt_bias"): ("m",),
    ("ssm", "a_log"): ("m", None),      # (d_inner, d_state)
    ("ssm", "d_skip"): ("m",),
    ("ssm", "w_out"): ("m", "f"),       # (d_inner, D)
    # RWKV6 ----------------------------------------------------------------
    ("rwkv", "w_r"): ("f", "m"),
    ("rwkv", "w_k"): ("f", "m"),
    ("rwkv", "w_v"): ("f", "m"),
    ("rwkv", "w_g"): ("f", "m"),
    ("rwkv", "w_o"): ("m", "f"),
    ("rwkv", "w_decay"): ("f", "m"),
    ("rwkv", "w_decay_lora_a"): ("f", None),
    ("rwkv", "w_decay_lora_b"): (None, "m"),
    ("rwkv", "u_bonus"): ("m",),
    ("rwkv", "mix"): (None, None),
    ("rwkv", "wk_ch"): ("f", "m"),      # channel-mix
    ("rwkv", "wv_ch"): ("m", "f"),
    ("rwkv", "wr_ch"): ("f", None),
}

def _leaf_spec(path: Tuple[str, ...], ndim: int, strategy: str) -> P:
    f = "data" if strategy == "fsdp_tp" else None
    key = None
    for i in range(len(path) - 1):
        if (path[i], path[-1]) in _RULES:
            key = (path[i], path[-1])
    if key is None and len(path) >= 2 and (path[-2], path[-1]) in _RULES:
        key = (path[-2], path[-1])
    if key is None:
        return P()  # norms, biases, scalars: replicated
    spec = tuple({"f": f, "m": "model"}.get(r, None) if isinstance(r, str) else None
                 for r in _RULES[key])
    if len(spec) > ndim:       # un-stacked single layer params
        spec = spec[-ndim:]
    pad = (None,) * (ndim - len(spec))
    return P(*(pad + spec))


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            names.append(str(p.key))
        else:
            names.append(str(p))
    return tuple(names)


def param_specs(params_shape: Any, cfg: ModelConfig) -> Any:
    """Pytree of PartitionSpec matching a pytree of ShapeDtypeStruct/arrays."""
    def one(path, leaf):
        return _leaf_spec(_path_names(path), len(leaf.shape), cfg.sharding)
    return jax.tree_util.tree_map_with_path(one, params_shape)


def param_shardings(params_shape: Any, cfg: ModelConfig, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                  param_specs(params_shape, cfg))


# ---------------------------------------------------------------------------
# activation / batch / cache specs
# ---------------------------------------------------------------------------

def data_batch_spec(mesh: Mesh, batch: int) -> P:
    axes = batch_shardable(mesh, batch)
    return P(axes, None) if axes else P(None, None)


def cache_pspec(cfg: ModelConfig, mesh: Mesh, batch: int, leaf_path: Tuple[str, ...],
                ndim: int) -> P:
    """Decode-cache sharding.

    Layout (L, B, W, ...) — batch over the data axes when divisible;
    otherwise the sequence (W) dim is sharded over them (flash-decoding);
    kv-heads over `model` when divisible, else W also takes `model`.

    Recurrent-state leaves (SSM/RWKV) have no W dim: their feature axis is
    `model`-sharded and batch over data when divisible.
    """
    baxes = batch_shardable(mesh, batch)
    leaf = leaf_path[-1]
    # --- recurrent / cross-attention states, dispatched by leaf name ------
    if leaf == "conv":          # (L, B, d_conv-1, d_inner)
        return P(None, baxes, None, "model")
    if leaf == "ssm":           # (L, B, d_inner, d_state)
        return P(None, baxes, "model", None)
    if leaf == "state":         # (L, B, H, hd, hd)  rwkv wkv state
        return P(None, baxes, "model", None, None)
    if leaf in ("tm_x", "cm_x"):  # (L, B, D)
        return P(None, baxes, "model")
    if leaf in ("xk", "xv"):    # (L, B, enc_seq, KV, hd)
        m = mesh_axis_size(mesh, "model")
        kv_ok = cfg.num_kv_heads % m == 0
        # enc_seq (1500) is not tile-friendly: replicate over `model`
        # unless the kv-heads divide.
        return P(None, baxes, None, "model" if kv_ok else None, None)
    m = mesh_axis_size(mesh, "model")
    kv_shardable = cfg.num_kv_heads % m == 0 and cfg.attention == "gqa"
    w_axes = []
    if baxes is None:
        w_axes.extend(batch_axes(mesh))
    if not kv_shardable:
        w_axes.append("model")
    spec = [None] * ndim
    # dims: (L, B, W, [KV, hd]) or (L, B, W, latent)
    b_dim, w_dim = ndim - 3 if ndim >= 4 else 1, ndim - 2 if ndim >= 4 else 2
    if ndim == 4:           # (L, B, W, latent) or (L, B, W, feat)
        b_dim, w_dim = 1, 2
    elif ndim == 5:         # (L, B, W, KV, hd)
        b_dim, w_dim = 1, 2
        if kv_shardable:
            spec[3] = "model"
    elif ndim == 3:         # (L, B, feat)  (ssm states)
        spec[1] = baxes
        spec[2] = "model"
        return P(*spec)
    if baxes:
        spec[b_dim] = baxes
    if w_axes:
        spec[w_dim] = tuple(w_axes) if len(w_axes) > 1 else w_axes[0]
    return P(*spec)
