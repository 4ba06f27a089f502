"""Forward operations of the dense decoder family (StableLM-3B-4E1T).

Per sequence of S tokens, width D, FFN F, vocabulary V, per layer:
  8 S D^2 (q, k, v, o) + 2 S^2 D (causal attention, half) + 6 S D F (SwiGLU);
plus the head, 2 S D V.
"""
from __future__ import annotations


def forward(c: dict, rows: int, seq_len: int) -> float:
    D, F, V, S = (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
                  seq_len)
    layer = 8 * S * D * D + 2 * S * S * D + 6 * S * D * F
    return float(rows * (c["num_hidden_layers"] * layer + 2 * S * D * V))
