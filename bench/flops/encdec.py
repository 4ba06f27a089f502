"""Forward operations of the encoder-decoder family (whisper-base).

Per sequence of S tokens over Se frames, width D, FFN F, vocabulary V:
  encoder layer: 8 Se D^2 (q, k, v, o) + 6 Se D F (SwiGLU)
                 + 4 Se^2 D (scores and values, bidirectional)
  decoder layer: 8 S D^2 + 2 S^2 D (causal self-attention, half)
                 + 4 S D^2 (cross q, o) + 4 Se D^2 (cross k, v over frames)
                 + 4 S Se D (cross scores and values) + 6 S D F
  head:          2 S D V
"""
from __future__ import annotations


def forward(c: dict, rows: int, seq_len: int) -> float:
    D, F, V = c["d_model"], c["encoder_ffn_dim"], c["vocab_size"]
    Se, S = c["max_source_positions"], seq_len
    enc = 8 * Se * D * D + 6 * Se * D * F + 4 * Se * Se * D
    dec = (8 * S * D * D + 2 * S * S * D + 4 * S * D * D + 4 * Se * D * D
           + 4 * S * Se * D + 6 * S * D * c["decoder_ffn_dim"])
    head = 2 * S * D * V
    return float(rows * (c["encoder_layers"] * enc + c["decoder_layers"] * dec
                         + head))
