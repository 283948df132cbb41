"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [
    # B, H, Hkv, D, T
    (1, 4, 4, 64, 128),
    (2, 8, 2, 64, 200),
    (2, 9, 3, 64, 321),
    (3, 16, 2, 80, 1000),
    (1, 32, 8, 128, 4096),
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_synapse_attention_matches_ref(shape, dtype):
    B, H, Hkv, D, T = shape
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, H, D)).astype(dtype)
    keys = jax.random.normal(ks[1], (B, T, Hkv, D)).astype(dtype)
    vals = jax.random.normal(ks[2], (B, T, Hkv, D)).astype(dtype)
    valid = jax.random.bernoulli(ks[3], 0.7, (B, T)).at[:, 0].set(True)
    out, mass = ops.synapse_attention(q, keys, vals, valid)
    out_r, mass_r = ref.synapse_attention_ref(q, keys, vals, valid)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(out_r, np.float32), **_tol(dtype)
    )
    np.testing.assert_allclose(np.asarray(mass), np.asarray(mass_r), **_tol(dtype))
    # probability mass conserves: sums to H per lane
    np.testing.assert_allclose(np.asarray(mass.sum(-1)), H, rtol=1e-3)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_landmark_score_matches_ref(shape, dtype):
    B, H, Hkv, D, T = shape
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, H, D)).astype(dtype)
    keys = jax.random.normal(ks[1], (B, T, Hkv, D)).astype(dtype)
    lm = jax.random.normal(ks[2], (B, 7, D)).astype(dtype)
    dens, dist = ops.landmark_score(q, keys, lm, block_t=128)
    logits_r, dist_r = ref.landmark_score_ref(q, keys, lm)
    dens_r = jax.nn.softmax(logits_r, -1).sum(1)
    np.testing.assert_allclose(np.asarray(dens), np.asarray(dens_r), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(dist), np.asarray(dist_r), **_tol(dtype))


def test_masked_keys_get_zero_mass():
    B, H, Hkv, D, T = 1, 4, 2, 64, 256
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    keys = jax.random.normal(ks[1], (B, T, Hkv, D))
    vals = jax.random.normal(ks[2], (B, T, Hkv, D))
    valid = jnp.zeros((B, T), bool).at[:, :10].set(True)
    _, mass = ops.synapse_attention(q, keys, vals, valid)
    assert float(mass[:, 10:].max()) < 1e-9
    np.testing.assert_allclose(float(mass.sum()), H, rtol=1e-4)


def test_kernel_used_in_synapse_decode_path_is_equivalent():
    """The pure-jnp decode_attend and the kernel agree — the engine may swap
    either in (ops.py is the serving hot path on TPU)."""
    from repro.models.attention import decode_attend

    B, H, Hkv, D, T = 2, 8, 4, 64, 96
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (B, H, D))
    keys = jax.random.normal(ks[1], (B, T, Hkv, D))
    vals = jax.random.normal(ks[2], (B, T, Hkv, D))
    valid = jnp.ones((B, T), bool)
    out_k, mass_k = ops.synapse_attention(q, keys, vals, valid)
    out_j, mass_j = decode_attend(q, keys, vals, valid)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_j), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mass_k), np.asarray(mass_j), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", DTYPES)
def test_synapse_attention_grid_variant_matches_ref(dtype):
    """The per-(batch, kv head) grid the chip compiles — rows for the mask
    and the mass, keys read in their cache layout — run under the
    interpreter at qwen2.5-0.5b's padded head widths."""
    from repro.kernels import synapse_attention as sa

    B, H, Hkv, D, T = 3, 14, 2, 128, 256
    ks = jax.random.split(jax.random.key(4), 4)
    q = jax.random.normal(ks[0], (B, H, D)).astype(dtype)
    keys = jax.random.normal(ks[1], (B, T, Hkv, D)).astype(dtype)
    vals = jax.random.normal(ks[2], (B, T, Hkv, D)).astype(dtype)
    valid = jax.random.bernoulli(ks[3], 0.6, (B, T)).at[:, 0].set(True)
    out, mass = sa.synapse_attention(q, keys, vals, valid, interpret=True, batched=False)
    out_r, mass_r = ref.synapse_attention_ref(q, keys, vals, valid)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(out_r, np.float32), **_tol(dtype)
    )
    np.testing.assert_allclose(np.asarray(mass), np.asarray(mass_r), **_tol(dtype))
    assert float(jnp.where(valid, 0.0, mass).max()) == 0.0


def test_landmark_score_multi_block_matches_ref():
    """Several key blocks per row: each block writes its own lane slice of
    the logits and distance rows."""
    from repro.kernels import landmark_score as ls

    B, H, Hkv, D, T = 2, 14, 2, 128, 512
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    keys = jax.random.normal(ks[1], (B, T, Hkv, D))
    lm = jax.random.normal(ks[2], (B, 7, D))
    logits, dist = ls.landmark_score(q, keys, lm, block_t=128, interpret=True)
    logits_r, dist_r = ref.landmark_score_ref(q, keys, lm)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_r), **_tol(jnp.float32))
    np.testing.assert_allclose(np.asarray(dist), np.asarray(dist_r), rtol=1e-4, atol=1e-4)
    dens_only, none = ls.landmark_score(q, keys, None, block_t=128, interpret=True)
    assert none is None
    np.testing.assert_array_equal(np.asarray(dens_only), np.asarray(logits))
