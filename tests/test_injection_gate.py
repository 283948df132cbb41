"""Referential Injection (§3.6) + Validation Gate (§3.5)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import gate as gate_lib
from repro.core import injection
from repro.models import model as model_lib


def _setup(arch="qwen3-8b"):
    cfg = dataclasses.replace(get_config(arch, reduced=True), compute_dtype="float32")
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def test_injection_changes_output_only_for_accepted_lanes():
    cfg, params = _setup()
    B, S = 2, 16
    tok = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    spec = model_lib.CacheSpec(kind="full", capacity=S + 16)
    caches = model_lib.init_caches(cfg, B, spec)
    _, _, caches = model_lib.prefill(params, cfg, {"tokens": tok}, caches, spec=spec)

    thought = jax.random.randint(jax.random.key(2), (B, 4), 0, cfg.vocab_size)
    vpos = jnp.full((B,), S, jnp.int32)
    th_caches, th_hidden = injection.encode_thought_kv(params, cfg, thought, vpos)
    accept = jnp.asarray([True, False])
    injected = injection.inject(cfg, caches, th_caches, accept)

    # lane 0 grew by 4, lane 1 untouched
    lengths = np.asarray(injected.groups[0].length)  # [L, B]
    assert (lengths[:, 0] == S + 4).all()
    assert (lengths[:, 1] == S).all()

    # next decode differs on lane 0, identical on lane 1
    step_tok = jnp.zeros((B,), jnp.int32)
    pos = jnp.full((B,), S, jnp.int32)
    lg_base, _, _ = model_lib.decode_step(
        params, cfg, {"tokens": step_tok, "positions": pos}, caches, spec=spec
    )
    lg_inj, _, _ = model_lib.decode_step(
        params, cfg, {"tokens": step_tok, "positions": pos}, injected, spec=spec
    )
    d0 = float(jnp.abs(lg_inj[0] - lg_base[0]).max())
    d1 = float(jnp.abs(lg_inj[1] - lg_base[1]).max())
    assert d0 > 1e-4, "accepted lane must feel the thought"
    assert d1 < 1e-6, "rejected lane must be untouched"


def test_injection_preserves_stream_positions():
    """The visible stream's positions are NOT shifted by injection — the
    thought lives at virtual positions (paper: 'non-intrusive')."""
    cfg, params = _setup()
    B, S = 1, 12
    tok = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    spec = model_lib.CacheSpec(kind="full", capacity=S + 16)
    caches = model_lib.init_caches(cfg, B, spec)
    _, _, caches = model_lib.prefill(params, cfg, {"tokens": tok}, caches, spec=spec)
    thought = jax.random.randint(jax.random.key(2), (B, 4), 0, cfg.vocab_size)
    vpos = jnp.full((B,), 1000, jnp.int32)  # clearly-virtual index
    th_caches, _ = injection.encode_thought_kv(params, cfg, thought, vpos)
    injected = injection.inject(cfg, caches, th_caches, jnp.asarray([True]))
    pos = np.asarray(injected.groups[0].pos)[0, 0]  # layer 0, lane 0
    assert (pos[:S] == np.arange(S)).all()          # stream untouched
    assert (pos[S : S + 4] == np.arange(1000, 1004)).all()  # virtual indices


def test_synapse_injection_slots():
    cfg, params = _setup()
    B, S = 1, 16
    spec = model_lib.CacheSpec(kind="synapse", n_landmarks=8, window=8, n_inject=4)
    caches = model_lib.init_caches(cfg, B, spec)
    thought = jax.random.randint(jax.random.key(2), (B, 3), 0, cfg.vocab_size)
    th_caches, _ = injection.encode_thought_kv(params, cfg, thought, jnp.full((B,), 50, jnp.int32))
    injected = injection.inject(cfg, caches, th_caches, jnp.asarray([True]))
    assert int(np.asarray(injected.groups[0].inj_count)[0, 0]) == 3
    # injected keys become visible to the next synapse decode step
    tok = jnp.zeros((B,), jnp.int32)
    lg0, _, _ = model_lib.decode_step(
        params, cfg, {"tokens": tok, "positions": jnp.zeros((B,), jnp.int32)}, caches, spec=spec
    )
    lg1, _, _ = model_lib.decode_step(
        params, cfg, {"tokens": tok, "positions": jnp.zeros((B,), jnp.int32)}, injected, spec=spec
    )
    assert float(jnp.abs(lg1 - lg0).max()) > 1e-5


def test_ssm_state_blend():
    cfg, params = _setup("rwkv6-1.6b")
    B, S = 1, 12
    tok = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    spec = model_lib.CacheSpec(kind="full", capacity=S)
    caches = model_lib.init_caches(cfg, B, spec)
    _, _, caches = model_lib.prefill(params, cfg, {"tokens": tok}, caches, spec=spec)
    thought = jax.random.randint(jax.random.key(2), (B, 4), 0, cfg.vocab_size)
    th_caches, _ = injection.encode_thought_kv(params, cfg, thought, jnp.zeros((B,), jnp.int32))
    injected = injection.inject(cfg, caches, th_caches, jnp.asarray([True]), beta=0.3)
    w0 = np.asarray(caches.groups[0].wkv)
    w1 = np.asarray(injected.groups[0].wkv)
    wt = np.asarray(th_caches.groups[0].wkv)
    np.testing.assert_allclose(w1, 0.7 * w0 + 0.3 * wt, rtol=1e-5, atol=1e-6)


def _main_caches(cfg, spec, B, cursors):
    """Main caches of ``B`` lanes with random contents, each lane's write
    cursor (``length``, or ``inj_count`` of a synapse) at ``cursors``."""
    caches = model_lib.init_caches(cfg, B, spec)
    leaves, tree = jax.tree.flatten(caches)
    keys = jax.random.split(jax.random.key(7), len(leaves))
    leaves = [jax.random.normal(k, a.shape, a.dtype) if jnp.issubdtype(a.dtype, jnp.floating)
              else a for k, a in zip(keys, leaves)]
    caches = jax.tree.unflatten(tree, leaves)
    cur = jnp.asarray(cursors, jnp.int32)
    field = "inj_count" if spec.kind == "synapse" else "length"
    groups = tuple(
        dataclasses.replace(g, **{field: jnp.broadcast_to(cur, getattr(g, field).shape)})
        if hasattr(g, field) else g for g in caches.groups)
    return dataclasses.replace(caches, groups=groups)


# (arch, main cache, cursors, thought length). Every batch holds three
# thoughts of lane 0 with a rejected one between the two accepted, one of
# lane 1, and padding rows; "full_cache" pushes both lanes past capacity.
MERGE_CASES = {
    "full": ("qwen3-8b", model_lib.CacheSpec(kind="full", capacity=48), [12, 9], 4),
    "synapse_ring": ("qwen3-8b", model_lib.CacheSpec(kind="synapse", n_landmarks=8, window=8,
                                                     n_inject=6), [1, 3], 4),
    "mla": ("deepseek-v2-236b", model_lib.CacheSpec(kind="full", capacity=48), [10, 7], 4),
    "state_blend": ("rwkv6-1.6b", model_lib.CacheSpec(kind="full", capacity=16), [0, 0], 4),
    "full_cache": ("qwen3-8b", model_lib.CacheSpec(kind="full", capacity=24), [17, 22], 4),
}


@pytest.mark.parametrize("case", list(MERGE_CASES))
def test_batched_merge_equals_the_chain_of_single_merges(case):
    """``merge_thoughts`` over a batch lands every thought where the same
    merges made one by one with ``merge_thought``, in batch order, would:
    the same gate, lengths and slots, and the same K/V."""
    arch, spec, cursors, T = MERGE_CASES[case]
    cfg, params = _setup(arch)
    B = 2
    caches = _main_caches(cfg, spec, B, cursors)
    vpos_lane = jnp.asarray([100, 140], jnp.int32)
    thoughts = jax.random.randint(jax.random.key(2), (4, T), 0, cfg.vocab_size)
    # lane 1's hidden state is its thought's own (score 1); lane 0's three
    # thoughts are ordered so the one that scores lowest sits between the
    # other two, and theta falls between it and the next
    _, th = injection.encode_thought_kv(params, cfg, thoughts, jnp.asarray([100] * 3 + [140]))
    hidden = jnp.stack([jax.random.normal(jax.random.key(3), (cfg.d_model,)), th[3]])
    s0 = np.asarray(gate_lib.cosine_score(jnp.broadcast_to(hidden[0], (3, cfg.d_model)), th[:3]))
    lo, a, b = np.argsort(s0)
    theta = float(s0[lo] + s0[a]) / 2
    order = [a, lo, 3, b]
    parent = [0, 0, 1, 0]
    K = 8  # padded as the engine pads: four padding rows, aimed at both lanes
    tokens = jnp.concatenate([thoughts[jnp.asarray(order)],
                              jax.random.randint(jax.random.key(4), (K - 4, T), 0, cfg.vocab_size)])
    parent_k = jnp.asarray(parent + [0, 1, 0, 1], jnp.int32)
    valid = jnp.arange(K) < 4

    merged, accept, score = jax.jit(
        lambda c, tok, par, vp, ok: injection.merge_thoughts(
            params, cfg, c, hidden, tok, par, vp, ok, theta),
    )(caches, tokens, parent_k, vpos_lane[parent_k], valid)

    chain = caches
    want_accept, want_score = [], []
    single = jax.jit(lambda c, tok, mask: injection.merge_thought(
        params, cfg, c, hidden, tok, vpos_lane, mask, theta))
    for k in range(4):
        chain, acc, sc = single(chain, jnp.tile(tokens[k][None], (B, 1)),
                                jnp.arange(B) == parent[k])
        want_accept.append(bool(acc[parent[k]]))
        want_score.append(float(sc[parent[k]]))

    assert np.asarray(accept).tolist() == [True, False, True, True] + [False] * 4
    assert want_accept == [True, False, True, True]
    np.testing.assert_allclose(np.asarray(score)[:4], want_score, rtol=1e-6, atol=1e-6)
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(merged),
                                 jax.tree.leaves(chain)):
        got, want = np.asarray(got), np.asarray(want)
        if jnp.issubdtype(got.dtype, jnp.floating):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                       err_msg=jax.tree_util.keystr(path))
        else:  # lengths, counts and the slots' positions: exact
            np.testing.assert_array_equal(got, want, err_msg=jax.tree_util.keystr(path))

    if case == "full_cache":
        # a full cache keeps its cursor running past the capacity, and its
        # last T slots hold the lane's last accepted thought: lane 0 writes
        # row 0 at 17, then row 3 clamps to 20 over row 0's tail; lane 1's
        # one thought clamps to 20 too
        full = merged.groups[0]
        assert (np.asarray(full.length) == [17 + 2 * T, 22 + T]).all()
        tk, _ = injection.encode_thought_kv(params, cfg, tokens[:4], vpos_lane[parent_k[:4]])
        got_k, th_k = np.asarray(full.k), np.asarray(tk.groups[0].k)
        np.testing.assert_allclose(got_k[:, 0, 17:20], th_k[:, 0, :3], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got_k[:, 0, 20:24], th_k[:, 3], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got_k[:, 1, 20:24], th_k[:, 2], rtol=1e-5, atol=1e-5)
        before = np.asarray(caches.groups[0].k)
        np.testing.assert_array_equal(got_k[:, 0, :17], before[:, 0, :17])
        np.testing.assert_array_equal(got_k[:, 1, :20], before[:, 1, :20])


@pytest.mark.parametrize("cursor,width,last", [
    ([17, 22], 4, 20),  # a full cache of 24 slots: starts clamp to its last 4
    ([1, 3], 4, 2),     # an inject ring of 6 slots, 4 a thought
    ([0, 0], 4, 0),     # a ring a thought fills: the last accepted wins
])
def test_merge_blocks_agree_where_they_overlap(cursor, width, last):
    """A scatter's order among windows that overlap is not defined on the
    chip, so the blocks of one lane that overlap must carry the same values
    there: each slot of every accepted row's block copies what the same
    merges made one by one would leave in that slot."""
    parent = jnp.asarray([0, 0, 1, 0, 1, 0], jnp.int32)
    accept = jnp.asarray([True, False, True, True, True, True])
    start, source, count = injection._placement(
        parent, accept, jnp.asarray(cursor, jnp.int32), width, last)
    start, source = np.asarray(start), np.asarray(source)
    # one by one: each accepted row writes its block at its clamped start
    want, cur = {}, list(cursor)
    for k, (p, ok) in enumerate(zip(np.asarray(parent), np.asarray(accept))):
        if ok:
            st = min(max(cur[p], 0), last)
            want.update({(int(p), st + t): k * width + t for t in range(width)})
            cur[p] += width
    got = {}
    for k, (p, ok) in enumerate(zip(np.asarray(parent), np.asarray(accept))):
        if ok:
            for t in range(width):
                got.setdefault((int(p), int(start[k]) + t), set()).add(int(source[k, t]))
    assert got == {slot: {row} for slot, row in want.items()}
    assert np.asarray(count).tolist() == [3, 2]


# ---------------------------------------------------------------------------
def test_gate_eq2():
    h = jnp.asarray([[1.0, 0.0], [1.0, 0.0], [1.0, 0.0]])
    t = jnp.asarray([[1.0, 0.0], [0.0, 1.0], [-1.0, 0.0]])
    accept, score = gate_lib.validate(h, t, theta=0.5)
    np.testing.assert_allclose(np.asarray(score), [1.0, 0.0, -1.0], atol=1e-6)
    assert np.asarray(accept).tolist() == [True, False, False]


def test_gate_scale_invariance():
    key = jax.random.key(0)
    h = jax.random.normal(key, (4, 32))
    t = jax.random.normal(jax.random.key(1), (4, 32))
    _, s1 = gate_lib.validate(h, t)
    _, s2 = gate_lib.validate(h * 100.0, t * 0.01)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-5)
