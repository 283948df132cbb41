"""The plain reference against the program's own prefill-then-decode path
at a reduced size on the CPU, in float32: a river with an injected
thought, and a side agent spawned from it that decodes past its window."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.models import qwen_dense
from bench.reference import qwen_dense as qd
from bench.reference import synapse as syn_ref
from bench.tests import tiny

TOL = 2e-4  # float32 on both sides; only the order of summation differs


@pytest.fixture(scope="module")
def setup():
    from repro.models.config import ModelConfig

    model = dict(tiny.MODEL, compute_dtype="float32")
    cfg = ModelConfig(**model)
    params = qwen_dense.make_params(2**32 + 3, model)
    return cfg, model, params, qd.Dims.of(model)


def _decode(cfg, params, caches, spec, tokens, start):
    from repro.models import model as model_lib

    out = []
    for i, t in enumerate(tokens):
        logits, _, caches = model_lib.decode_step(
            params, cfg, {"tokens": jnp.asarray([t], jnp.int32),
                          "positions": jnp.asarray([start + i], jnp.int32)},
            caches, spec=spec)
        out.append(logits[0])
    return jnp.stack(out), caches


def test_river_with_injected_thought(setup):
    from repro.core import injection
    from repro.models import model as model_lib

    cfg, model, params, dims = setup
    rng = np.random.default_rng(0)
    prompt = [257] + list(rng.integers(0, 256, 40))
    P = len(prompt)
    spec = model_lib.CacheSpec(kind="full", capacity=128)
    _, hidden, caches = model_lib.prefill(
        params, cfg, {"tokens": jnp.asarray([prompt], jnp.int32)},
        model_lib.init_caches(cfg, 1, spec), spec=spec)
    inputs = [prompt[-1]] + list(rng.integers(0, 512, 9))
    first, caches = _decode(cfg, params, caches, spec, inputs[:6], P)
    thought = list(rng.integers(0, 256, 4))
    caches, accept, _ = injection.merge_thought(
        params, cfg, caches, hidden, jnp.asarray([thought], jnp.int32),
        jnp.asarray([P + 6], jnp.int32), jnp.asarray([True]), -1.0)
    assert bool(accept[0])
    second, _ = _decode(cfg, params, caches, spec, inputs[6:], P + 6)
    with jax.default_matmul_precision("highest"):
        ref, _ = qd.river_logits(params, dims, qd.F32, prompt, inputs, [(6, thought)])
    got = jnp.concatenate([first, second])
    assert float(jnp.max(jnp.abs(got - ref))) < TOL
    # the thought matters: without it the later logits move
    with jax.default_matmul_precision("highest"):
        plain, _ = qd.river_logits(params, dims, qd.F32, prompt, inputs, [])
    assert float(jnp.max(jnp.abs(got[6:] - plain[6:]))) > 100 * TOL


def test_side_agent_spawn_and_decode_past_its_window(setup):
    from repro.core.engine import spawn_caches
    from repro.core.synapse import SynapsePolicy
    from repro.models import model as model_lib

    cfg, model, params, dims = setup
    syn = tiny.SYNAPSE
    rng = np.random.default_rng(1)
    prompt = [257] + list(rng.integers(0, 256, 60))
    P = len(prompt)
    full = model_lib.CacheSpec(kind="full", capacity=96)
    _, _, caches = model_lib.prefill(
        params, cfg, {"tokens": jnp.asarray([prompt], jnp.int32)},
        model_lib.init_caches(cfg, 1, full), spec=full)
    side = model_lib.CacheSpec(
        kind="synapse", n_landmarks=syn["n_landmarks"], window=syn["window"],
        n_inject=syn["n_inject"],
        policy=SynapsePolicy(alpha=syn["alpha"], score_ema=syn["score_ema"],
                             coverage_cap=syn["coverage_cap"]))
    side_caches = spawn_caches(cfg, caches, side)
    inputs = list(rng.integers(0, 512, 3 * syn["window"]))  # graduates 2 windows
    got, _ = _decode(cfg, params, side_caches, side, inputs, P)
    with jax.default_matmul_precision("highest"):
        _, pkv = qd.river_logits(params, dims, qd.F32, prompt, [prompt[-1]], [])
        ref, gap, kept = syn_ref.side_logits(params, dims, qd.F32, pkv, syn, inputs, P)
        picks = np.asarray(side_caches.groups[0].lm_pos[:, 0])
        judged, picks_gap, _ = syn_ref.side_logits(params, dims, qd.F32, pkv, syn, inputs, P,
                                                   picks)
    assert float(jnp.max(jnp.abs(got - ref))) < TOL
    # the program's spawn kept the reference's landmarks: no selection gap
    assert (np.sort(picks, axis=1) == kept).all() and gap == 0.0 and picks_gap == 0.0
    assert float(jnp.max(jnp.abs(judged - ref))) == 0.0
    # a different landmark set is judged by how far its picks fall behind
    wrong = np.tile(np.arange(syn["n_landmarks"]), (picks.shape[0], 1))
    _, wrong_gap, _ = syn_ref.side_logits(params, dims, qd.F32, pkv, syn, inputs, P, wrong)
    assert wrong_gap > 0.05


def test_fp8_control_reads_far_from_the_reference(setup):
    cfg, model, params, dims = setup
    rng = np.random.default_rng(2)
    prompt = [257] + list(rng.integers(0, 256, 30))
    inputs = list(rng.integers(0, 512, 20))
    with jax.default_matmul_precision("highest"):
        ref, _ = qd.river_logits(params, dims, qd.F32, prompt, inputs, [])
        low, _ = qd.river_logits(params, dims, qd.FP8, prompt, inputs, [])
    err = float(jnp.max(jnp.abs(ref - low)))
    assert err > 100 * TOL
