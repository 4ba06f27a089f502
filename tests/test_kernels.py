"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle in
ref.py, swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.quantize import BLOCK, ROW_TILE

SHAPES = [(BLOCK * ROW_TILE,), (BLOCK * ROW_TILE * 3,), (999,), (1, 1),
          (123, 45), (BLOCK,), (2 * BLOCK + 17,)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _rand(shape, dtype, seed=0):
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_quantize_int8_matches_ref(shape, dtype):
    x = _rand(shape, dtype)
    q, s, n = ops.quantize_int8(x)
    out = ops.dequantize_int8(q, s, n)
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % (BLOCK * ROW_TILE)
    padded = jnp.concatenate([flat, jnp.zeros((pad,))]) if pad else flat
    q_ref, s_ref = ref.quantize_int8(padded, BLOCK)
    # bf16 inputs land exactly on .5 rounding boundaries after upcast, where
    # interpret-mode and XLA-jnp tie-breaking may differ by one step
    atol_q = 1 if dtype == jnp.bfloat16 else 0
    diff = np.abs(np.asarray(q, np.int32).reshape(-1)
                  - np.asarray(q_ref, np.int32))
    assert diff.max() <= atol_q, f"max int8 diff {diff.max()}"
    np.testing.assert_allclose(np.asarray(s).reshape(-1), np.asarray(s_ref),
                               rtol=1e-6)
    # quantization error bound: half a quantization step per element
    scale_full = np.repeat(np.asarray(s_ref), BLOCK)[:flat.size]
    err = np.abs(np.asarray(out) - np.asarray(flat))
    assert np.all(err <= 0.5 * scale_full + 1e-7)


@pytest.mark.parametrize("shape", SHAPES)
def test_ternarize_matches_ref(shape):
    x = _rand(shape, jnp.float32, seed=1)
    t, s, n = ops.ternarize(x)
    flat = x.reshape(-1)
    pad = (-flat.size) % (BLOCK * ROW_TILE)
    padded = jnp.concatenate([flat, jnp.zeros((pad,))]) if pad else flat
    t_ref, s_ref = ref.ternarize(padded, BLOCK)
    np.testing.assert_array_equal(np.asarray(t).reshape(-1), np.asarray(t_ref))
    np.testing.assert_allclose(np.asarray(s).reshape(-1), np.asarray(s_ref),
                               rtol=1e-6)
    assert set(np.unique(np.asarray(t))) <= {-1, 0, 1}


@pytest.mark.parametrize("ratio", [0.01, 0.1, 0.5])
@pytest.mark.parametrize("n", [4096, 100_000])
def test_topk_sparsify(ratio, n):
    x = _rand((n,), jnp.float32, seed=2)
    y = ops.topk_sparsify(x, ratio)
    kept = int(jnp.sum(y != 0))
    k = max(int(ratio * n), 1)
    assert abs(kept - k) <= max(2, int(0.01 * n)), (kept, k)
    # exactly the largest-magnitude entries survive
    y_ref = ref.topk_mask(x, ref.topk_threshold(x, ratio))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


@pytest.mark.parametrize("k", [1, 2, 8, 16])
@pytest.mark.parametrize("n", [2048, 5000])
def test_fused_add(k, n):
    bufs = _rand((k, n), jnp.float32, seed=3)
    out = ops.fused_add(bufs)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.fused_add(bufs)),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# property tests
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 5000), seed=st.integers(0, 2**31 - 1),
       scale=st.floats(1e-3, 1e3))
def test_quantize_roundtrip_error_bounded(n, seed, scale):
    """|dequant(quant(x)) - x| <= max|block| / 254 for every element."""
    x = _rand((n,), jnp.float32, seed=seed) * scale
    q, s, m = ops.quantize_int8(x)
    out = ops.dequantize_int8(q, s, m)
    err = np.abs(np.asarray(out) - np.asarray(x))
    bound = float(jnp.max(jnp.abs(x))) / 254.0 + 1e-6
    assert err.max() <= bound * 1.01


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 3000), seed=st.integers(0, 2**31 - 1))
def test_ternary_sign_preserved(n, seed):
    x = _rand((n,), jnp.float32, seed=seed)
    t, s, m = ops.ternarize(x)
    tt = np.asarray(t).ravel()[:n]
    xx = np.asarray(x)
    nz = tt != 0
    assert np.all(np.sign(xx[nz]) == tt[nz])


@settings(max_examples=20, deadline=None)
@given(k=st.integers(1, 10), n=st.integers(1, 4000),
       seed=st.integers(0, 2**31 - 1))
def test_fused_add_linearity(k, n, seed):
    bufs = _rand((k, n), jnp.float32, seed=seed)
    out2 = ops.fused_add(2.0 * bufs)
    out1 = ops.fused_add(bufs)
    np.testing.assert_allclose(np.asarray(out2), 2 * np.asarray(out1),
                               rtol=1e-5, atol=1e-5)


def test_interpret_mode_only_on_cpu(monkeypatch):
    """Kernels are compiled on TPU, interpreted on the CPU backend, and
    refused anywhere else rather than silently interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret()
