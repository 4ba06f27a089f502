"""stablelm-3b — StableLM-3B-4E1T, MHA (kv=heads) dense decoder
[hf:stabilityai/stablelm-3b-4e1t, its config.json]

Published widths: hidden 2560, 32 layers, 32 heads of 80 (32 KV heads),
SwiGLU FFN 6912, vocabulary 50304, untied embeddings, 4096-token context,
rope theta 1e4.  The repo's block departs from the published one (as
``bench/configs/stablelm-3b-4e1t.json`` lists):
- RMSNorm instead of LayerNorm;
- full rotary embedding on every head dimension instead of 25% partial
  rotary;
- no biases;
- weight decay on every stored leaf of two or more dimensions, so on the
  layer-stacked norm gains too.
"""
from repro.configs import register
from repro.configs.base import ModelConfig


@register("stablelm-3b")
def config() -> ModelConfig:
    return ModelConfig(
        name="stablelm-3b", family="dense", num_layers=32, d_model=2560,
        num_heads=32, num_kv_heads=32, d_ff=6912, vocab_size=50304,
        sharding="dp_tp", source="hf:stabilityai/stablelm-3b-4e1t")
