"""Operations a step needs, counted from shapes, and the chip's peaks.

A training step needs three forward passes' worth of operations (the
forward, and twice it in the backward); recomputation (remat) is not
counted.  Each family file gives ``forward(config, rows, seq_len)``, the
operations of one forward pass over ``rows`` sequences: two per
multiply-add of every matrix product, embedding lookups and elementwise
work left out.
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def forward(config: dict, rows: int, seq_len: int) -> float:
    fam = importlib.import_module(f"bench.flops.{config['family']}")
    return fam.forward(config, rows, seq_len)


def step(config: dict, rows: int, seq_len: int) -> float:
    return 3.0 * forward(config, rows, seq_len)


def peak(device_kind: str, table: dict | None = None) -> dict:
    """The peaks of ``device_kind``: ``bf16_flops`` (FLOP/s) and
    ``hbm_bytes_per_s``.  A kind that is not in the table is an error."""
    table = table if table is not None else json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def flash_attention(bh: int, seq_len: int, head_dim: int, itemsize: int = 2,
                    causal: bool = True) -> tuple[float, float]:
    """(operations, bytes) one call of the flash-attention forward kernel
    needs: q k^T and p v over (bh) heads, half of the score matrix when
    causal; q, k, v read and o written once."""
    ops = 4.0 * bh * seq_len * seq_len * head_dim * (0.5 if causal else 1.0)
    return ops, 4.0 * bh * seq_len * head_dim * itemsize
