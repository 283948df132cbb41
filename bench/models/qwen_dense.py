"""Seeded random weights for a dense Qwen2/Qwen3 configuration, made on the
device in one jitted call, in the dtype they are served in.

The benchmark owns its weights: the program under test and the plain
reference both read this tree, so the reference takes nothing the program
made. The tree has the layout the program's ``models.model`` expects (a
CPU test compares it with the program's own abstract parameters).
Biases and norm scales are drawn away from their neutral values (0 and 1)
so that the comparison with the reference sees every term of the block.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


@partial(jax.jit, static_argnames=("dims",))
def _make(key, dims):
    L, d, H, Hkv, D, ff, V, bias, qk_norm, tied, dtype = dims
    ks = iter(jax.random.split(key, 16))
    cast = lambda a: a.astype(dtype)
    attn = {
        "wq": cast(_normal(next(ks), (L, d, H * D), d ** -0.5)),
        "wk": cast(_normal(next(ks), (L, d, Hkv * D), d ** -0.5)),
        "wv": cast(_normal(next(ks), (L, d, Hkv * D), d ** -0.5)),
        "wo": cast(_normal(next(ks), (L, H * D, d), (H * D) ** -0.5)),
    }
    if bias:
        attn["bq"] = cast(_normal(next(ks), (L, H * D), 0.3))
        attn["bk"] = cast(_normal(next(ks), (L, Hkv * D), 0.3))
        attn["bv"] = cast(_normal(next(ks), (L, Hkv * D), 0.3))
    if qk_norm:
        attn["q_norm"] = cast(1.0 + _normal(next(ks), (L, D), 0.1))
        attn["k_norm"] = cast(1.0 + _normal(next(ks), (L, D), 0.1))
    layers = {
        "ln1": cast(1.0 + _normal(next(ks), (L, d), 0.1)),
        "ln2": cast(1.0 + _normal(next(ks), (L, d), 0.1)),
        "attn": attn,
        "mlp": {
            "gate": cast(_normal(next(ks), (L, d, ff), d ** -0.5)),
            "up": cast(_normal(next(ks), (L, d, ff), d ** -0.5)),
            "down": cast(_normal(next(ks), (L, ff, d), ff ** -0.5)),
        },
    }
    params = {
        "embed": cast(_normal(next(ks), (V, d), 0.02)),
        "groups": [layers],
        "final_norm": cast(1.0 + _normal(next(ks), (d,), 0.1)),
    }
    if not tied:
        params["head"] = cast(_normal(next(ks), (d, V), d ** -0.5))
    return params


def make_params(seed: int, model: dict):
    """The parameter tree for ``model`` (a configuration file's
    ``model_config`` block) from ``seed``, on the default device."""
    dims = (
        model["n_layers"], model["d_model"], model["n_heads"], model["n_kv_heads"],
        model["d_head"], model["d_ff"], model["vocab_size"], bool(model["qkv_bias"]),
        bool(model["qk_norm"]), bool(model["tie_embeddings"]), jnp.dtype(model["param_dtype"]),
    )
    # fold the seed into a key by halves so any non-negative seed (> 2**32
    # included) gives a distinct, reproducible stream
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return _make(key, dims)
