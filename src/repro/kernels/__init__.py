"""Pallas TPU kernels (+ pure-jnp oracles in ref.py, wrappers in ops.py).

Compression hot-spots the paper's §3.2 varies: quantize (int8/ternary),
topk_mask, fused_add.  Model kernels: wkv (RWKV6), ssm_scan (Mamba
selective scan); attention runs JAX's own splash-attention kernel,
dispatched from ``repro.models.attention``.
All validated in interpret mode against the oracles; model dispatch via
``ModelConfig.use_pallas``.
"""
