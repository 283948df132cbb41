"""One-hot cache primitives + piece_attend == reference attend (the §Perf
flash-decode path must be numerically identical on one device), plus the
scoped ShardContext API that replaced the old set_shard_axis module global
(ISSUE 6): entering/exiting a context must never leak into later traces."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import synapse_sharded as sh
from repro.models.attention import decode_attend


def test_onehot_write_read_roundtrip():
    buf = jnp.zeros((3, 8, 2, 4))
    new = jnp.ones((3, 2, 4)) * jnp.arange(1, 4)[:, None, None]
    slot = jnp.asarray([0, 3, 7])
    out = sh.onehot_write(buf, slot, new)
    back = sh.onehot_read(out, slot)
    np.testing.assert_allclose(np.asarray(back), np.asarray(new))
    # untouched slots remain zero
    assert float(out.sum()) == float(new.sum())


def test_onehot_write_mask():
    buf = jnp.zeros((2, 4))
    out = sh.onehot_write(buf, jnp.asarray([1, 2]), jnp.asarray([5.0, 7.0]),
                          mask=jnp.asarray([True, False]))
    assert float(out[0, 1]) == 5.0 and float(out[1, 2]) == 0.0


def _stack(a):
    """[B,T,Hkv,D] -> the synapse cache's stack of one layer, [1,B,T,Hkv*D]."""
    return a.reshape((1,) + a.shape[:2] + (-1,))


def test_piece_attend_matches_decode_attend():
    B, H, Hkv, D = 2, 8, 4, 32
    ks = jax.random.split(jax.random.key(0), 7)
    q = jax.random.normal(ks[0], (B, H, D))
    sizes = [16, 8, 4]
    pieces, valids = [], []
    for i, T in enumerate(sizes):
        k = jax.random.normal(ks[1 + i], (B, T, Hkv, D))
        v = jax.random.normal(ks[4 + i], (B, T, Hkv, D))
        pieces.append((k, v))
        valids.append(jax.random.bernoulli(ks[i], 0.8, (B, T)).at[:, 0].set(True))
    scale = 1.0 / (D ** 0.5)
    stacks = [(_stack(k), _stack(v)) for k, v in pieces]
    out, masses = sh.piece_attend(q, stacks, valids, scale, layer=0)

    keys = jnp.concatenate([k for k, _ in pieces], axis=1)
    vals = jnp.concatenate([v for _, v in pieces], axis=1)
    valid = jnp.concatenate(valids, axis=1)
    out_ref, mass_ref = decode_attend(q, keys, vals, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(masses, 1)), np.asarray(mass_ref), rtol=1e-5, atol=1e-5
    )


def test_token_sharding_scope_is_leak_proof():
    """The context manager restores the previous placement on exit AND on
    error — the failure mode of the old module global (one test setting it
    poisoned every later trace in the interpreter)."""
    assert sh.get_shard_axis() is None
    with sh.token_sharding("model", mesh="fake-mesh"):
        assert sh.get_shard_axis() == "model"
        assert sh.current_context().mesh == "fake-mesh"
        with sh.token_sharding(None):  # nested scopes override and restore
            assert sh.get_shard_axis() is None
        assert sh.get_shard_axis() == "model"
    assert sh.get_shard_axis() is None
    with pytest.raises(RuntimeError):
        with sh.token_sharding("model"):
            raise RuntimeError("boom")
    assert sh.get_shard_axis() is None


def test_explicit_ctx_overrides_ambient_scope():
    """Callers that thread a ShardContext (the engine's policy path) are
    immune to whatever ambient scope is live: an explicit local ctx under a
    sharded scope still takes the exact-scatter fast path."""
    buf = jnp.zeros((3, 8, 2, 4))
    new = jnp.ones((3, 2, 4))
    slot = jnp.asarray([0, 3, 7])
    local = sh.ShardContext()
    with sh.token_sharding("model", mesh="fake-mesh"):
        out = sh.onehot_write(buf, slot, new, ctx=local)
        back = sh.onehot_read(out, slot, ctx=local)
    np.testing.assert_allclose(np.asarray(back), np.asarray(new))


def test_onehot_sharded_formulation_matches_scatter():
    """The one-hot select/contract (used when a token axis is live) equals
    the plain scatter/gather fast path bit-for-bit on in-bounds slots —
    onehot needs no collective, so an axis-bearing ctx without a mesh
    exercises it on one device."""
    key = jax.random.key(3)
    buf = jax.random.normal(key, (4, 8, 2, 4))
    new = jax.random.normal(jax.random.key(4), (4, 2, 4))
    slot = jnp.asarray([0, 5, 7, 2])
    mask = jnp.asarray([True, False, True, True])
    oh_ctx = sh.ShardContext(axis="model")  # no mesh: onehot is collective-free
    a = sh.onehot_write(buf, slot, new, mask=mask)
    b = sh.onehot_write(buf, slot, new, mask=mask, ctx=oh_ctx)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(sh.onehot_read(buf, slot)),
        np.asarray(sh.onehot_read(buf, slot, ctx=oh_ctx)),
    )


def test_piece_attend_requires_mesh_with_axis():
    q = jnp.zeros((1, 4, 8))
    k = _stack(jnp.zeros((1, 4, 2, 8)))
    valid = jnp.ones((1, 4), bool)
    with pytest.raises(ValueError, match="no mesh"):
        sh.piece_attend(q, [(k, k)], [valid], 0.5, layer=0,
                        ctx=sh.ShardContext(axis="model"))


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >=2 devices")
def test_piece_attend_sharded_matches_local():
    """The psum flash-decode over a token-sharded mesh matches the local
    fused path (rtol: the combine reorders the softmax reductions)."""
    mesh = jax.make_mesh((2,), ("model",))
    B, H, Hkv, D = 2, 4, 2, 16
    ks = jax.random.split(jax.random.key(7), 5)
    q = jax.random.normal(ks[0], (B, H, D))
    pieces, valids = [], []
    for i, T in enumerate((8, 4)):
        k = jax.random.normal(ks[1 + i], (B, T, Hkv, D))
        v = jax.random.normal(ks[3 + i], (B, T, Hkv, D))
        pieces.append((_stack(k), _stack(v)))
        valids.append(jnp.ones((B, T), bool).at[:, -1].set(i == 0))
    scale = 1.0 / (D ** 0.5)
    out_l, mass_l = sh.piece_attend(q, pieces, valids, scale, layer=0)
    out_s, mass_s = sh.piece_attend(
        q, pieces, valids, scale, layer=0, ctx=sh.ShardContext("model", mesh)
    )
    np.testing.assert_allclose(np.asarray(out_l), np.asarray(out_s), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(mass_l, 1)),
        np.asarray(jnp.concatenate(mass_s, 1)), rtol=1e-5, atol=1e-6,
    )
