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


# (H, Hkv, D): qwen2.5-0.5b's and qwen3-4b's widths; pieces are the side
# cache's landmarks / window / inject slots
PIECE_SHAPES = {"qwen2.5-0.5b": (14, 2, 64), "qwen3-4b": (32, 8, 128)}
PIECE_SIZES = (64, 64, 16)


def _piece_lanes(model, dtype) -> int:
    """24 lanes take three grid steps of 8. Eight lanes of qwen3-4b's f32
    rows overflow a step's VMEM, so that case takes 4 lanes, one block."""
    return 4 if (model, dtype) == ("qwen3-4b", jnp.float32) else 24


def _piece_masks(key, B, sizes):
    """Prefix fills per lane, as the decode's counters give them: lane 0
    full, lane 1 a single valid slot (its window's first), lane 2 an empty
    inject piece, the rest random."""
    counts = []
    for i, T in enumerate(sizes):
        c = jax.random.randint(jax.random.fold_in(key, i), (B,), 0, T + 1)
        c = c.at[0].set(T).at[1].set(1 if i == 1 else 0)
        if i == 2:
            c = c.at[2].set(0)
        counts.append(c)
    return [jnp.arange(T)[None, :] < c[:, None] for T, c in zip(sizes, counts)]


@pytest.mark.parametrize("model", list(PIECE_SHAPES))
@pytest.mark.parametrize("dtype", DTYPES)
def test_synapse_attention_pieces_matches_ref(model, dtype):
    """The piece-wise kernel's grid (the lane blocks it picks, every kv
    head in a row, one softmax over the three pieces, K/V read at one layer
    of their stacks) under the interpreter, against the oracle on the
    joined key set of that layer."""
    from repro.kernels import synapse_attention as sa

    H, Hkv, D = PIECE_SHAPES[model]
    B, NL, layer = _piece_lanes(model, dtype), 3, 1
    ks = jax.random.split(jax.random.key(6), 8)
    q = jax.random.normal(ks[0], (B, H, D)).astype(dtype)
    stacks = [tuple(jax.random.normal(ks[1 + 2 * i + j], (NL, B, T, Hkv * D)).astype(dtype)
                    for j in range(2)) for i, T in enumerate(PIECE_SIZES)]
    valids = _piece_masks(ks[7], B, PIECE_SIZES)
    assert sa.fits_in_place(B, H, PIECE_SIZES, Hkv * D, jnp.dtype(dtype).itemsize)
    out, masses = sa.synapse_attention_pieces(
        q, stacks, valids, jnp.int32(layer), interpret=True)

    pieces = [(k[layer], v[layer]) for k, v in stacks]
    heads = lambda a: a.reshape(B, -1, Hkv, D)
    out_r, mass_r = ref.synapse_attention_ref(
        q, jnp.concatenate([heads(k) for k, _ in pieces], 1),
        jnp.concatenate([heads(v) for _, v in pieces], 1), jnp.concatenate(valids, 1))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(out_r, np.float32), **_tol(dtype)
    )
    splits = np.cumsum(PIECE_SIZES)[:-1]
    for mass, m_r, valid in zip(masses, np.split(np.asarray(mass_r), splits, 1), valids):
        assert mass.dtype == jnp.float32 and mass.shape == valid.shape
        np.testing.assert_allclose(np.asarray(mass), m_r, **_tol(dtype))
        assert float(jnp.where(valid, 0.0, mass).max()) == 0.0
    total = sum(np.asarray(m, np.float64).sum(-1) for m in masses)
    np.testing.assert_allclose(total, H, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(masses[1][1, 0]), H, rtol=1e-5)


@pytest.mark.parametrize("B,H,sizes,width,itemsize,fits", [
    (256, 14, (64, 64, 16), 2 * 64, 2, True),    # qwen2.5-0.5b council lanes
    (64, 32, (64, 64, 16), 8 * 128, 2, True),    # qwen3-4b council lanes
    (64, 32, (64, 64, 16), 8 * 128, 4, False),   # qwen3-4b in f32: 8 lanes overflow VMEM
    (256, 14, (64, 64, 1), 2 * 64, 2, False),    # n_inject 0 keeps one slot: J = 1
    (256, 14, (64, 64, 16), 3 * 64, 2, False),   # a row of 192 lanes
    (4, 14, (64, 64, 16), 2 * 64, 2, True),      # fewer lanes than a block: one step
], ids=["qwen2.5-0.5b", "qwen3-4b", "qwen3-4b_f32", "no_inject", "width_192", "four_lanes"])
def test_synapse_pieces_predicate(B, H, sizes, width, itemsize, fits):
    from repro.kernels import synapse_attention as sa

    assert sa.fits_in_place(B, H, sizes, width, itemsize) is fits


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
