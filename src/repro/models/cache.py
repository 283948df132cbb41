"""Decode-time state pytrees.

All caches are fixed-shape (XLA static shapes): growth is expressed as a
write cursor, eviction as index arithmetic. Per-batch-lane lengths support
continuous batching (lanes at different positions).

Cache kinds
-----------
* FullCache      — standard KV cache [B, S, Hkv, D] with per-lane cursor.
* SynapseCache   — the paper's Topological Synapse as a *streaming* cache:
                   K landmark slots + W recent-window ring + J referential-
                   injection slots. O(K+W+J) per agent instead of O(L).
* MLACache       — DeepSeek-V2 latent cache (c_kv + shared rope key).
* Mamba2State    — conv tail + SSD state (O(1)).
* RWKV6State     — token-shift tails + wkv matrix state (O(1)).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


def _register(cls):
    fields = [f for f in cls.__dataclass_fields__]
    jax.tree_util.register_dataclass(cls, data_fields=fields, meta_fields=[])
    return cls


@_register
@dataclass
class FullCache:
    k: jax.Array       # [B, S, Hkv, D]
    v: jax.Array       # [B, S, Hkv, D]
    pos: jax.Array     # [B, S] int32 — rope position of each slot
    score: jax.Array   # [B, S] f32 — accumulated attention mass (density EMA)
    length: jax.Array  # [B] int32 — write cursor / valid prefix

    @property
    def capacity(self) -> int:
        return self.k.shape[1]


@_register
@dataclass
class SynapseCache:
    # K/V are stored lane-dense, [B, T_i, Hkv*D]: the kv heads side by side
    # in one row, the layout the side pass's attend kernel reads in place.
    # landmark region (the "Topological Synapse")
    lm_k: jax.Array      # [B, K, Hkv*D]
    lm_v: jax.Array      # [B, K, Hkv*D]
    lm_pos: jax.Array    # [B, K] int32
    lm_score: jax.Array  # [B, K] f32 — accumulated hybrid density-coverage score
    lm_count: jax.Array  # [B] int32 — populated landmark slots
    # recent window ring
    win_k: jax.Array     # [B, W, Hkv*D]
    win_v: jax.Array     # [B, W, Hkv*D]
    win_pos: jax.Array   # [B, W] int32
    win_score: jax.Array # [B, W] f32 — attention mass accumulated while resident
    # referential injection slots (paper §3.6)
    inj_k: jax.Array     # [B, J, Hkv*D]
    inj_v: jax.Array     # [B, J, Hkv*D]
    inj_pos: jax.Array   # [B, J] int32
    inj_count: jax.Array # [B] int32
    win_count: jax.Array # [B] int32 — tokens written into the ring (fill state)
    length: jax.Array    # [B] int32 — total stream tokens seen

    # slot counts read off the K rows' second-minor dim, so they hold for
    # one layer's synapse and for the stack of every layer's alike
    @property
    def n_landmarks(self) -> int:
        return self.lm_k.shape[-2]

    @property
    def window(self) -> int:
        return self.win_k.shape[-2]

    @property
    def n_inject(self) -> int:
        return self.inj_k.shape[-2]


@_register
@dataclass
class MLACache:
    ckv: jax.Array     # [B, S, r] latent
    krope: jax.Array   # [B, S, d_rope] shared rope key
    score: jax.Array   # [B, S] f32 — accumulated attention mass (density EMA)
    length: jax.Array  # [B]

    @property
    def capacity(self) -> int:
        return self.ckv.shape[1]


@_register
@dataclass
class Mamba2State:
    conv: jax.Array  # [B, conv_width-1, d_conv_ch] — conv input tail
    ssm: jax.Array   # [B, n_heads, d_head, d_state] f32


@_register
@dataclass
class RWKV6State:
    shift_tm: jax.Array  # [B, d_model] — previous token (time-mix)
    shift_cm: jax.Array  # [B, d_model] — previous token (channel-mix)
    wkv: jax.Array       # [B, H, head, head] f32 matrix state


# ---------------------------------------------------------------------------
# constructors
# ---------------------------------------------------------------------------
def init_full_cache(cfg: ModelConfig, batch: int, capacity: int, dtype=None) -> FullCache:
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    hkv, d = cfg.n_kv_heads, cfg.d_head
    z = lambda *s: jnp.zeros(s, dtype)
    return FullCache(
        k=z(batch, capacity, hkv, d),
        v=z(batch, capacity, hkv, d),
        pos=jnp.zeros((batch, capacity), jnp.int32),
        score=jnp.zeros((batch, capacity), jnp.float32),
        length=jnp.zeros((batch,), jnp.int32),
    )


def init_synapse_cache(
    cfg: ModelConfig,
    batch: int,
    n_landmarks: int,
    window: int,
    n_inject: int = 0,
    dtype=None,
) -> SynapseCache:
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    hkv, d = cfg.n_kv_heads, cfg.d_head
    z = lambda *s: jnp.zeros(s, dtype)
    zi = lambda *s: jnp.zeros(s, jnp.int32)
    zf = lambda *s: jnp.zeros(s, jnp.float32)
    return SynapseCache(
        lm_k=z(batch, n_landmarks, hkv * d),
        lm_v=z(batch, n_landmarks, hkv * d),
        lm_pos=zi(batch, n_landmarks),
        lm_score=jnp.full((batch, n_landmarks), -jnp.inf, jnp.float32),
        lm_count=zi(batch),
        win_k=z(batch, window, hkv * d),
        win_v=z(batch, window, hkv * d),
        win_pos=zi(batch, window),
        win_score=zf(batch, window),
        inj_k=z(batch, max(n_inject, 1), hkv * d),
        inj_v=z(batch, max(n_inject, 1), hkv * d),
        inj_pos=zi(batch, max(n_inject, 1)),
        inj_count=zi(batch),
        win_count=zi(batch),
        length=zi(batch),
    )


def init_mla_cache(cfg: ModelConfig, batch: int, capacity: int, dtype=None) -> MLACache:
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    return MLACache(
        ckv=jnp.zeros((batch, capacity, cfg.kv_lora_rank), dtype),
        krope=jnp.zeros((batch, capacity, cfg.qk_rope_head_dim), dtype),
        score=jnp.zeros((batch, capacity), jnp.float32),
        length=jnp.zeros((batch,), jnp.int32),
    )


def init_mamba2_state(cfg: ModelConfig, batch: int, dtype=None) -> Mamba2State:
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    d_conv_ch = cfg.ssm_d_inner + 2 * cfg.ssm_state_size
    return Mamba2State(
        conv=jnp.zeros((batch, cfg.ssm_conv_width - 1, d_conv_ch), dtype),
        ssm=jnp.zeros((batch, cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_state_size), jnp.float32),
    )


def init_rwkv6_state(cfg: ModelConfig, batch: int, dtype=None) -> RWKV6State:
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    h, hs = cfg.rwkv_n_heads, cfg.rwkv_head_size
    return RWKV6State(
        shift_tm=jnp.zeros((batch, cfg.d_model), dtype),
        shift_cm=jnp.zeros((batch, cfg.d_model), dtype),
        wkv=jnp.zeros((batch, h, hs, hs), jnp.float32),
    )


def cache_bytes(cache) -> int:
    """Exact live bytes of a cache pytree (the paper's 'VRAM per agent')."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(cache))
