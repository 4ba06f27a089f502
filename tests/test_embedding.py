"""The shared embedding lookup (``models/layers.embed_lookup``): its forward
is the plain gather, and its gradient sums each row's cotangents in float32
and rounds once, where a bfloat16 scatter-add rounds after every addend."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.layers import embed_lookup

ROWS, WIDTH, TOKENS = 4096, 64, 4096
BF16_ROUNDING = 2.0 ** -8        # largest relative error of one rounding


def _zipf_ids(n, vocab, a, seed):
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -a
    return np.random.default_rng(seed).choice(vocab, size=n, p=p / p.sum())


def _table_and_batch(seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    table = (jax.random.normal(k1, (ROWS, WIDTH)) * 0.02).astype(jnp.bfloat16)
    ids = jnp.asarray(_zipf_ids(TOKENS, ROWS, 1.2, seed), jnp.int32)
    g = jax.random.normal(k2, (TOKENS, WIDTH), jnp.float32).astype(jnp.bfloat16)
    return table, ids, g


def _plain_gather(table, ids):
    return table[ids]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_lookup_forward_is_the_gather(dtype):
    table, ids, _ = _table_and_batch()
    table = table.astype(dtype)
    got = jax.jit(embed_lookup)(table, ids.reshape(64, 64))
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(table[ids.reshape(64, 64)]))


@pytest.mark.parametrize("lookup,within", [(embed_lookup, True),
                                           (_plain_gather, False)],
                         ids=["embed_lookup", "plain_bf16_gather"])
def test_gradient_is_the_f32_row_sum_rounded_once(lookup, within):
    """A Zipf(1.2) batch of 4096 tokens over a bfloat16 table of 4096 rows:
    every row of the gradient lies within one bfloat16 rounding of the
    float32 ``segment_sum`` of its tokens' cotangents.  The plain gather's
    transpose, a bfloat16 scatter-add, misses that bound on its frequent
    rows (the most frequent takes about a fifth of the batch)."""
    table, ids, g = _table_and_batch()
    _, vjp = jax.vjp(lambda t: lookup(t, ids), table)
    (grad,) = jax.jit(vjp)(g)
    assert grad.dtype == jnp.bfloat16 and grad.shape == table.shape
    ref = np.asarray(jax.ops.segment_sum(g.astype(jnp.float32), ids,
                                         num_segments=ROWS))
    err = np.linalg.norm(np.asarray(grad, np.float32) - ref, axis=1)
    bound = BF16_ROUNDING * np.linalg.norm(ref, axis=1)
    assert bool(np.all(err <= bound)) is within, (err / np.maximum(bound, 1e-30)).max()


def test_gradient_of_a_token_seen_once_is_its_cotangent():
    table, _, g = _table_and_batch()
    ids = jnp.arange(0, 2 * 64, 2, dtype=jnp.int32)        # each row once
    _, vjp = jax.vjp(lambda t: embed_lookup(t, ids), table)
    (grad,) = vjp(g[:64])
    np.testing.assert_array_equal(np.asarray(grad[ids]), np.asarray(g[:64]))
    assert not np.asarray(grad[1::2], np.float32).any()
