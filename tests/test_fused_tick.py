"""Fused-tick engine invariants (ISSUE 3 acceptance criteria):

* parity: N fused ticks produce the same tokens/cache state as N legacy
  per-step decodes (greedy sampling, fixed seed), for main AND side lanes;
* drain cadence does not change results (greedy);
* tick() issues exactly ONE jitted dispatch and ZERO blocking host syncs
  between drains when sync_every > 1;
* synapse_decode output matches between the Pallas kernel and the
  piece_attend (sharded) fallback.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import synapse as synapse_lib
from repro.core.engine import CortexEngine
from repro.core.prism import Prism
from repro.core.router import CortexRouter
from repro.data.tokenizer import ByteTokenizer
from repro.models import attention, cache as cache_lib
from repro.models import model as model_lib
from repro.serving.sampler import SamplingParams


def _cfg():
    return dataclasses.replace(
        get_config("qwen2.5-0.5b", reduced=True), compute_dtype="float32"
    )


def _engine(cfg, params, *, sync_every=1, max_side=1, theta=2.0, side_max_steps=64):
    prism = Prism(params, cfg)
    tok = ByteTokenizer(cfg.vocab_size)
    return CortexEngine(
        prism, tok, n_main=1, max_side=max_side, main_capacity=128,
        side_max_steps=side_max_steps, inject_tokens=8, theta=theta,
        sampling=SamplingParams(greedy=True), sync_every=sync_every,
    )


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def test_fused_tick_matches_legacy_main_decode(setup):
    """Greedy main-lane stream == reference prefill + per-step decode_step
    chain (the legacy two-dispatch formulation), including the cache."""
    cfg, params = setup
    eng = _engine(cfg, params, sync_every=4)
    prompt = "the quick brown fox"
    m = eng.submit(prompt, lane=0)
    ids = list(m.tokens)
    n = 8
    eng.run(n)

    spec = model_lib.CacheSpec(kind="full", capacity=128)
    caches = model_lib.init_caches(cfg, 1, spec)
    toks = jnp.asarray([ids], jnp.int32)
    logits, _, caches = model_lib.prefill(params, cfg, {"tokens": toks}, caches, spec=spec)
    ref = list(ids)
    pos = len(ids)
    for _ in range(n):
        logits, _, caches = model_lib.decode_step(
            params, cfg,
            {"tokens": jnp.asarray([ref[-1]], jnp.int32), "positions": jnp.asarray([pos], jnp.int32)},
            caches, spec=spec,
        )
        ref.append(int(jnp.argmax(logits[0])))
        pos += 1

    assert m.tokens == ref
    # cache parity: same K/V prefix written
    eng_cache = eng.main_caches.groups[0]
    ref_cache = caches.groups[0]
    length = int(np.asarray(ref_cache.length)[0, 0])
    assert int(np.asarray(eng_cache.length)[0, 0]) == length
    np.testing.assert_allclose(
        np.asarray(eng_cache.k[:, :, :length], np.float32),
        np.asarray(ref_cache.k[:, :, :length], np.float32),
        rtol=1e-5, atol=1e-5,
    )


def test_drain_cadence_is_invisible_greedy(setup):
    """sync_every=1 vs sync_every=4 must produce identical main streams."""
    cfg, params = setup
    outs = []
    for sync_every in (1, 4):
        eng = _engine(cfg, params, sync_every=sync_every)
        m = eng.submit("parity probe", lane=0)
        eng.run(8)
        outs.append(list(m.tokens))
    assert outs[0] == outs[1]


def test_fused_tick_matches_legacy_side_decode(setup):
    """Side-lane stream (teacher-forced prompt then free-running greedy) ==
    reference decode_step chain over the spawn-time synapse snapshot."""
    cfg, params = setup
    eng = _engine(cfg, params, sync_every=1, side_max_steps=64)
    eng.submit("context context [TASK: think hard] tail", lane=0)
    s = next(s for s in eng.sides if s.active)
    # deep copy: the live buffers are donated away by subsequent ticks
    snapshot = jax.tree.map(lambda a: jnp.array(a, copy=True), eng.side_caches)
    prompt_ids = list(s.tokens)
    pos0 = s.position
    n = len(prompt_ids) + 6  # cover teacher forcing AND free generation
    eng.run(n)

    caches = snapshot
    plen = len(prompt_ids)
    ref_generated = []
    last = prompt_ids[-1]
    for t in range(n):
        in_tok = prompt_ids[t] if t < plen else last
        logits, _, caches = model_lib.decode_step(
            params, cfg,
            {"tokens": jnp.asarray([in_tok], jnp.int32),
             "positions": jnp.asarray([pos0 + t], jnp.int32)},
            caches, spec=eng.side_spec,
        )
        samp = int(jnp.argmax(logits[0]))
        if t >= plen - 1:
            ref_generated.append(samp)
            last = samp
    assert s.tokens[plen:] == ref_generated[: len(s.tokens) - plen]
    assert len(s.tokens) > plen  # the stream actually generated tokens


def test_tick_is_one_dispatch_zero_syncs(setup):
    """Acceptance: with sync_every > 1, tick() = exactly one jitted dispatch
    and no blocking host transfer; drain happens every sync_every ticks."""
    cfg, params = setup
    eng = _engine(cfg, params, sync_every=4)
    eng.submit("dispatch counting", lane=0)
    for _ in range(4):  # warm the SINGLE-tick jit + a drain (run() would
        eng.tick()      # warm the scanned macro path instead)
    base = dict(eng.stats)
    # transfer_guard makes the "no blocking transfer" invariant real: any
    # implicit device<->host traffic inside tick() raises, independent of
    # the engine's self-reported counters.
    with jax.transfer_guard("disallow"):
        for i in range(3):  # ticks 1..3 of a window: no drain
            eng.tick()
    assert eng.stats["tick_dispatches"] - base["tick_dispatches"] == 3
    assert eng.stats["host_syncs"] == base["host_syncs"]
    assert eng.stats["drains"] == base["drains"]
    assert eng.stats["aux_dispatches"] == base["aux_dispatches"]
    eng.tick()  # 4th tick closes the window
    assert eng.stats["tick_dispatches"] - base["tick_dispatches"] == 4
    assert eng.stats["drains"] == base["drains"] + 1
    assert eng.stats["host_syncs"] == base["host_syncs"] + 1


def test_lifecycle_with_batched_drain(setup):
    """Spawn + merge still work when control runs at drain granularity."""
    cfg, params = setup
    eng = _engine(cfg, params, sync_every=4, max_side=2, theta=-1.0, side_max_steps=6)
    eng.submit("hello [TASK: verify this claim] world", lane=0)
    eng.run(48)  # prompt forcing (~25 ticks) + 6 generated + drain slack
    events = [e["event"] for e in eng.history]
    assert "spawn" in events
    merge = next(e for e in eng.history if e["event"] == "merge")
    assert merge["accepted"] is True  # theta = -1 accepts everything


def test_synapse_decode_pallas_matches_piece():
    """The Pallas attend (default) and piece_attend (sharded fallback) give
    the same decode output and cache update."""
    cfg = _cfg()
    params = attention.attn_init(jax.random.key(0), cfg, jnp.float32)
    B, K, W, J = 3, 16, 8, 4
    cache = cache_lib.init_synapse_cache(cfg, B, K, W, J, jnp.float32)
    ks = jax.random.split(jax.random.key(1), 6)
    cache = dataclasses.replace(
        cache,
        lm_k=jax.random.normal(ks[0], cache.lm_k.shape),
        lm_v=jax.random.normal(ks[1], cache.lm_v.shape),
        lm_score=jax.random.uniform(ks[2], cache.lm_score.shape),
        lm_count=jnp.asarray([0, 5, K], jnp.int32),
        win_k=jax.random.normal(ks[3], cache.win_k.shape),
        win_v=jax.random.normal(ks[4], cache.win_v.shape),
        win_count=jnp.asarray([2, W, W + 3], jnp.int32),
        length=jnp.asarray([2, W + 5, K + W + 3], jnp.int32),
    )
    x = jax.random.normal(ks[5], (B, 1, cfg.d_model))
    positions = jnp.asarray([3, 40, 90], jnp.int32)
    stack = jax.tree.map(lambda a: a[None], cache)  # the decode scan's stack of one layer
    outs = {}
    for impl in ("pallas", "piece"):
        policy = synapse_lib.SynapsePolicy(attend_impl=impl)
        y, new_cache, stats = synapse_lib.synapse_decode(
            params, cfg, x, stack, positions, policy, layer=0
        )
        outs[impl] = (y, new_cache, stats)
    y_p, c_p, st_p = outs["pallas"]
    y_j, c_j, st_j = outs["piece"]
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_j), rtol=1e-5, atol=1e-5)
    for leaf_p, leaf_j in zip(jax.tree.leaves(c_p), jax.tree.leaves(c_j)):
        np.testing.assert_allclose(
            np.asarray(leaf_p, np.float32), np.asarray(leaf_j, np.float32),
            rtol=1e-5, atol=1e-5,
        )
    np.testing.assert_allclose(
        np.asarray(st_p["attn_mass_landmarks"]), np.asarray(st_j["attn_mass_landmarks"]),
        rtol=1e-5, atol=1e-5,
    )


def test_router_feed_incremental_exactly_once():
    r = CortexRouter()
    assert r.feed("a", "xy [TAS") == []
    trig = r.feed("a", "K: joined] z")
    assert [t.kind for t in trig] == ["task"]
    assert trig[0].payload == "joined"
    assert r.feed("a", "") == []          # tail rescan must not re-fire
    assert r.feed("a", " more text") == []
    trig = r.feed("a", " [DONE]")
    assert [t.kind for t in trig] == ["done"]
