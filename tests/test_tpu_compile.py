"""The codec kernels and the attention kernels compile for a TPU v5e,
checked without one.

Each Pallas kernel of the explicit gradient exchange is compiled at a 64 MiB
fusion bucket, and whisper-base's three attentions (forward and backward) at
the benchmark's shapes, for one chip of a described (not attached)
``v5e:2x2`` topology, and must come out as Mosaic ``tpu_custom_call``s: what
interpret mode on the CPU cannot show (tiling, VMEM limits, lowering).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all import
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_add import fused_add_2d
from repro.kernels.quantize import (BLOCK, dequantize_int8_2d,
                                    quantize_int8_2d, ternarize_2d)
from repro.kernels.topk_mask import topk_mask_2d
from repro.models import attention

BUCKET_ROWS = 64 * 1024 * 1024 // 4 // BLOCK      # 64 MiB of f32 = (65536, 256)
BUCKET_ELEMS = BUCKET_ROWS * BLOCK


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without the chip, so keep these compiles out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _rows(dtype, sharding, cols=BLOCK, rows=BUCKET_ROWS):
    return jax.ShapeDtypeStruct((rows, cols), dtype, sharding=sharding)


@pytest.mark.parametrize("kernel", ["quantize_int8_2d", "ternarize_2d",
                                    "dequantize_int8_2d", "topk_mask_2d"])
def test_codec_kernel_compiles_for_v5e(one_chip, kernel):
    f32 = _rows(jnp.float32, one_chip)
    if kernel == "quantize_int8_2d":
        text = _compiled_text(quantize_int8_2d, f32)
    elif kernel == "ternarize_2d":
        text = _compiled_text(ternarize_2d, f32)
    elif kernel == "dequantize_int8_2d":
        text = _compiled_text(dequantize_int8_2d,
                              _rows(jnp.int8, one_chip),
                              _rows(jnp.float32, one_chip, cols=1))
    else:
        thr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
        text = _compiled_text(topk_mask_2d, f32, thr)
    assert 'custom_call_target="tpu_custom_call"' in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_fused_add_compiles_for_v5e(one_chip, dtype):
    """K=4 buffers of one bucket each: the reduction of a 4-chip exchange."""
    buffers = jax.ShapeDtypeStruct((4, BUCKET_ELEMS), dtype,
                                   sharding=one_chip)
    text = _compiled_text(fused_add_2d, buffers)
    assert 'custom_call_target="tpu_custom_call"' in text


@pytest.mark.parametrize("Sq,Skv,causal", [(1500, 1500, False),
                                           (448, 1500, False),
                                           (448, 448, True)],
                         ids=["encoder", "cross", "decoder"])
def test_attention_kernels_compile_for_v5e(one_chip, monkeypatch, Sq, Skv,
                                           causal):
    """32 x 8 heads x 64, bf16: the forward kernel and the fused backward
    kernel, and no f32 score matrix left in the compiled gradient."""
    # the described chip is not the default backend: compile, not interpret
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = lambda s: jax.ShapeDtypeStruct((32, 8, s, 64), jnp.bfloat16,
                                           sharding=one_chip)

    def loss(q, k, v):
        out = attention._flash_pallas(q, k, v, causal)
        return jnp.sum(out.astype(jnp.float32))

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), shape(Sq),
                          shape(Skv), shape(Skv))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert f"f32[32,8,{Sq},{Skv}]" not in text
