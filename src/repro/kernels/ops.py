"""Public wrappers over the Pallas compression kernels.

Handles 1-D <-> tiled-2-D layout, padding to tile multiples, and backend
dispatch: on TPU the kernels run compiled; on the CPU backend (tests) they
run with ``interpret=True``, which executes the kernel body in Python —
bit-identical semantics, validated against ``ref.py``.  Any other backend
is an error, never a silent interpret-mode run.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import fused_add as _fa
from repro.kernels import quantize as _q
from repro.kernels import ref
from repro.kernels import topk_mask as _tm

BLOCK = _q.BLOCK
_ROW = _q.ROW_TILE
_PAD_UNIT = BLOCK * _ROW


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas codec kernels run compiled on TPU or interpreted on "
            f"CPU; the default backend is {backend!r}")
    return backend == "cpu"


def _to_rows(x: jnp.ndarray) -> Tuple[jnp.ndarray, int]:
    """Flatten + zero-pad to a (R, BLOCK) grid with R % ROW_TILE == 0."""
    n = x.size
    flat = x.reshape(n)
    pad = (-n) % _PAD_UNIT
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, BLOCK), n


def quantize_int8(x: jnp.ndarray, interpret: bool | None = None):
    """x: any shape float -> (q int8 (R, BLOCK), scales (R, 1), n)."""
    rows, n = _to_rows(x.astype(jnp.float32))
    q, s = _q.quantize_int8_2d(rows, interpret=_interpret() if interpret is None else interpret)
    return q, s, n


def dequantize_int8(q: jnp.ndarray, s: jnp.ndarray, n: int,
                    interpret: bool | None = None) -> jnp.ndarray:
    out = _q.dequantize_int8_2d(
        q, s, interpret=_interpret() if interpret is None else interpret)
    return out.reshape(-1)[:n]


def ternarize(x: jnp.ndarray, interpret: bool | None = None):
    rows, n = _to_rows(x.astype(jnp.float32))
    t, s = _q.ternarize_2d(rows, interpret=_interpret() if interpret is None else interpret)
    return t, s, n


def deternarize(t: jnp.ndarray, s: jnp.ndarray, n: int,
                interpret: bool | None = None) -> jnp.ndarray:
    out = _q.dequantize_int8_2d(      # dequant kernel is scale-multiply; reuse
        t, s, interpret=_interpret() if interpret is None else interpret)
    return out.reshape(-1)[:n]


def topk_sparsify(x: jnp.ndarray, ratio: float, sample: int = 0,
                  interpret: bool | None = None) -> jnp.ndarray:
    """DGC-style sparsification: keep the ~ratio largest-magnitude entries.

    ``sample > 0`` estimates the threshold from that many strided samples
    (the DGC trick — avoids a full sort over a 64 MB bucket).
    """
    flat = x.reshape(-1)
    n = flat.size
    if sample and sample < n:
        stride = n // sample
        thr = ref.topk_threshold(flat[::stride], ratio)
    else:
        thr = ref.topk_threshold(flat, ratio)
    rows, _ = _to_rows(flat)
    out = _tm.topk_mask_2d(rows, thr,
                           interpret=_interpret() if interpret is None else interpret)
    return out.reshape(-1)[:n].reshape(x.shape).astype(x.dtype)


def fused_add(buffers: jnp.ndarray) -> jnp.ndarray:
    """buffers: (K, n) -> (n,) f32 sum via the fused Pallas reduction."""
    K, n = buffers.shape
    pad = (-n) % _fa.COL_TILE
    if pad:
        buffers = jnp.concatenate(
            [buffers, jnp.zeros((K, pad), buffers.dtype)], axis=1)
    out = _fa.fused_add_2d(buffers, interpret=_interpret())
    return out[0, :n]
