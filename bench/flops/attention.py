"""Operations of a step's attention alone: q k^T and p v of every attention
of the model, counted from the shapes, unpadded, half of the score matrix
where attention is causal.  Three forward passes' worth a step, as in
``bench/flops``, whatever implements the attention and however often it
recomputes it.

  decoder:  per layer 2 S^2 D (causal self-attention: 4 S^2 D, halved)
  encdec:   per encoder layer 4 Se^2 D (bidirectional); per decoder layer
            2 S^2 D (causal) + 4 S Se D (cross-attention over the frames)

over ``rows`` sequences of S tokens (and Se frames), width D = heads x
head dimension.
"""
from __future__ import annotations


def forward(c: dict, rows: int, seq_len: int) -> float:
    S = seq_len
    if c["family"] == "decoder":
        return float(rows * c["num_hidden_layers"] * 2 * S * S * c["hidden_size"])
    if c["family"] == "encdec":
        D, Se = c["d_model"], c["max_source_positions"]
        enc = 4 * Se * Se * D
        dec = 2 * S * S * D + 4 * S * Se * D
        return float(rows * (c["encoder_layers"] * enc + c["decoder_layers"] * dec))
    raise ValueError(f"no attention count for family {c['family']!r}")


def step(c: dict, rows: int, seq_len: int) -> float:
    return 3.0 * forward(c, rows, seq_len)
