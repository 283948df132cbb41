"""Per-architecture smoke tests (deliverable f): reduced variant of each
assigned config runs one forward AND one train step on CPU; output shapes
and finiteness asserted. Full configs are exercised only via the dry-run."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, list_archs
from repro.data.pipeline import DataConfig, make_batch
from repro.models import model as model_lib
from repro.training.optimizer import AdamWConfig
from repro.training.trainer import init_train_state, make_train_step

ARCHS = list_archs()


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_forward(arch):
    cfg = get_config(arch, reduced=True)
    assert cfg.n_layers <= 2 and cfg.d_model <= 512
    if cfg.is_moe:
        assert cfg.n_experts <= 4
    params = model_lib.init_params(jax.random.key(0), cfg)
    B, S = 2, 32
    if cfg.embed_inputs:
        inputs = {"tokens": jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)}
    else:
        inputs = {"embeds": jax.random.normal(jax.random.key(1), (B, S, cfg.d_model))}
    logits, aux = model_lib.forward(params, cfg, inputs)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_train_step(arch):
    cfg = get_config(arch, reduced=True)
    state = init_train_state(jax.random.key(0), cfg)
    step = jax.jit(make_train_step(cfg, AdamWConfig(warmup_steps=1, total_steps=10)))
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, DataConfig(seq_len=32, batch_size=2)).items()}
    new_state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert int(new_state.step) == 1
    # params actually changed
    delta = max(
        float(jnp.abs(a - b).max())
        for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(new_state.params))
    )
    assert delta > 0.0


def test_exact_assigned_configs():
    """The full configs match the assignment table exactly."""
    rows = {
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "qwen2-vl-72b": (80, 8192, 64, 8, 29568, 152064),
        "rwkv6-1.6b": (24, 2048, 32, 32, 7168, 65536),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "qwen1.5-110b": (80, 8192, 64, 8, 49152, 152064),
        "qwen3-8b": (36, 4096, 32, 8, 12288, 151936),
        "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
        "deepseek-v2-236b": (60, 5120, 128, 128, 1536, 102400),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "smollm-135m": (30, 576, 9, 3, 1536, 49152),
    }
    for arch, (L, d, h, kv, ff, v) in rows.items():
        cfg = get_config(arch)
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab_size) == (
            L, d, h, kv, ff, v,
        ), arch
    assert get_config("zamba2-1.2b").ssm_state_size == 64
    assert get_config("qwen3-moe-30b-a3b").n_experts == 128
    assert get_config("qwen3-moe-30b-a3b").experts_per_token == 8
    ds = get_config("deepseek-v2-236b")
    assert ds.kv_lora_rank == 512 and ds.n_experts == 160 and ds.experts_per_token == 6
    assert ds.n_shared_experts == 2 and ds.attn_kind == "mla"
    assert get_config("qwen2-vl-72b").rope_kind == "mrope"
    assert not get_config("hubert-xlarge").causal


def test_param_counts_plausible():
    """Analytic counts land near the advertised sizes."""
    approx = {
        "smollm-135m": (0.134e9, 0.35),
        "qwen3-8b": (8.2e9, 0.35),
        "qwen3-4b": (4.02e9, 0.1),
        "qwen1.5-110b": (111e9, 0.25),
        "deepseek-v2-236b": (236e9, 0.35),
        "qwen3-moe-30b-a3b": (30.5e9, 0.35),
        "rwkv6-1.6b": (1.6e9, 0.5),
        "zamba2-1.2b": (1.2e9, 0.6),
    }
    for arch, (target, tol) in approx.items():
        n = get_config(arch).param_count()
        assert abs(n - target) / target < tol, (arch, n, target)


def test_moe_active_params():
    cfg = get_config("qwen3-moe-30b-a3b")
    active = cfg.active_param_count()
    assert active < cfg.param_count() * 0.25
    assert 2e9 < active < 5e9  # "A3B"
