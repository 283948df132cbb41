"""Referential Injection (paper §3.6).

A side agent's accepted thought is encoded by a forward pass (shared
weights — the Prism) and its per-layer K/V are appended to the main agent's
caches at *virtual* RoPE positions, so the main stream's token sequence and
positions are untouched: the model "remembers" the thought without reading
it. Static-shape adaptation (DESIGN.md §3): caches are pre-allocated; full
caches receive injected K/V at the write cursor, synapse caches in their
dedicated ``inj_*`` slots.

For attention-free layers (RWKV6 / Mamba2 state), injection is re-expressed
as a *state blend*: the thought is run forward and its terminal recurrent
state is mixed into the main state (beta-weighted). This is the closest
TPU/SSM-idiomatic equivalent — documented as an adaptation in DESIGN.md §4.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import gate as gate_lib
from repro.models import cache as cache_lib
from repro.models import model as model_lib
from repro.models.config import ModelConfig


def encode_thought_kv(params, cfg: ModelConfig, thought_tokens, virtual_pos):
    """Run a forward pass over the thought and capture per-layer K/V.

    thought_tokens: [B, T] int32; virtual_pos: [B] — the virtual positional
    index assigned to the thought (paper: "auxiliary context").
    Returns the ModelCaches of a throwaway prefill with capacity == T, whose
    full caches hold exactly the rotated K/V of the thought, plus the
    terminal hidden state [B, d] (used by the Validation Gate).
    """
    B, T = thought_tokens.shape
    positions = virtual_pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    if cfg.rope_kind == "mrope":
        positions = jnp.broadcast_to(positions[:, None, :], (B, 3, T))
    spec = model_lib.CacheSpec(kind="full", capacity=T)
    caches = model_lib.init_caches(cfg, B, spec)
    logits, hidden, caches = model_lib.prefill(
        params, cfg, {"tokens": thought_tokens, "positions": positions}, caches, spec=spec
    )
    return caches, hidden


def _append_lanes(dst, src, start, axis: int):
    """Per-lane dynamic append: dst [L,B,S,...], src [L,B,T,...], start [B]."""
    def per_lane(d, s, st):  # d: [L,S,...], s: [L,T,...]
        return jax.lax.dynamic_update_slice_in_dim(d, s.astype(d.dtype), st, axis=axis)
    return jax.vmap(per_lane, in_axes=(1, 1, 0), out_axes=1)(dst, src, start)


def inject_full(main: cache_lib.FullCache, thought: cache_lib.FullCache, accept):
    """Append thought K/V into a stacked FullCache group.

    main.*: [L, B, S, ...]; thought.*: [L, B, T, ...]; accept: [B] bool.
    The injected slots get the thought's (virtual) positions; length grows by
    T for accepted lanes.
    """
    T = thought.k.shape[2]
    start = main.length[0]  # [B] — all layers share lane lengths
    new_k = _append_lanes(main.k, thought.k, start, axis=1)
    new_v = _append_lanes(main.v, thought.v, start, axis=1)
    new_pos = _append_lanes(main.pos, thought.pos, start, axis=1)
    new_score = _append_lanes(main.score, thought.score, start, axis=1)
    acc = accept[None, :, None, None, None]
    sel = lambda n, o: jnp.where(jnp.reshape(accept, (1, -1) + (1,) * (n.ndim - 2)), n, o)
    new_len = jnp.where(accept, main.length + T, main.length)
    return cache_lib.FullCache(
        k=sel(new_k, main.k),
        v=sel(new_v, main.v),
        pos=sel(new_pos, main.pos),
        score=sel(new_score, main.score),
        length=jnp.broadcast_to(new_len, main.length.shape),
    )


def inject_mla(main: cache_lib.MLACache, thought: cache_lib.MLACache, accept):
    T = thought.ckv.shape[2]
    start = main.length[0]
    new_ckv = _append_lanes(main.ckv, thought.ckv, start, axis=1)
    new_krope = _append_lanes(main.krope, thought.krope, start, axis=1)
    new_score = _append_lanes(main.score, thought.score, start, axis=1)
    sel = lambda n, o: jnp.where(jnp.reshape(accept, (1, -1) + (1,) * (n.ndim - 2)), n, o)
    new_len = jnp.where(accept, main.length + T, main.length)
    return cache_lib.MLACache(
        ckv=sel(new_ckv, main.ckv),
        krope=sel(new_krope, main.krope),
        score=sel(new_score, main.score),
        length=jnp.broadcast_to(new_len, main.length.shape),
    )


def inject_synapse(main: cache_lib.SynapseCache, thought: cache_lib.FullCache, accept, max_tokens: int | None = None):
    """Write thought K/V into the synapse's dedicated injection slots.

    Thought tokens beyond the J slots are dropped oldest-first (the slots are
    a ring). thought.*: [L, B, T, ...] from encode_thought_kv.
    """
    J = main.inj_k.shape[2]
    T = thought.k.shape[2]
    take = min(T, J)
    lane_rows = lambda a: a.reshape(a.shape[:3] + (-1,))  # the synapse's [.., Hkv*D] rows
    th_k = lane_rows(thought.k[:, :, -take:])
    th_v = lane_rows(thought.v[:, :, -take:])
    th_pos = thought.pos[:, :, -take:]
    start = jnp.minimum(main.inj_count[0], J - take)  # [B]
    new_k = _append_lanes(main.inj_k, th_k, start, axis=1)
    new_v = _append_lanes(main.inj_v, th_v, start, axis=1)
    new_pos = _append_lanes(main.inj_pos, th_pos, start, axis=1)
    sel = lambda n, o: jnp.where(jnp.reshape(accept, (1, -1) + (1,) * (n.ndim - 2)), n, o)
    new_count = jnp.where(accept, jnp.minimum(main.inj_count + take, J), main.inj_count)
    return dataclasses.replace(
        main,
        inj_k=sel(new_k, main.inj_k),
        inj_v=sel(new_v, main.inj_v),
        inj_pos=sel(new_pos, main.inj_pos),
        inj_count=jnp.broadcast_to(new_count, main.inj_count.shape),
    )


def blend_state(main_state, thought_state, accept, beta: float = 0.3):
    """SSM adaptation: mix the thought's terminal recurrent state into the
    main agent's state. main/thought: stacked [L, B, ...] state pytrees."""
    def mix(m, t):
        acc = jnp.reshape(accept, (1, -1) + (1,) * (m.ndim - 2))
        blended = (1.0 - beta) * m.astype(jnp.float32) + beta * t.astype(jnp.float32)
        return jnp.where(acc, blended.astype(m.dtype), m)
    return jax.tree.map(mix, main_state, thought_state)


def merge_thought(
    params,
    cfg: ModelConfig,
    main_caches,
    main_hidden,
    thought_tokens,
    virtual_pos,
    lane_mask,
    theta: float,
    beta: float = 0.3,
):
    """Encode + Validation Gate + Referential Injection as ONE fused step.

    The legacy merge path issued three dispatches (encode_thought_kv, gate,
    inject); fused, a merge costs a single drain-time dispatch with the main
    caches donated. Note the gate decision is a traced value, so the thought
    prefill and the masked inject are always computed — a rejected merge is
    cheaper in dispatches, not in FLOPs (a host-side early-out would need
    the gate score synced back first).
    Returns (new_main_caches, accept [B] bool, score [B] f32).
    """
    thought_caches, t_hidden = encode_thought_kv(params, cfg, thought_tokens, virtual_pos)
    accept_vec, score = gate_lib.validate(main_hidden, t_hidden, theta)
    accept = accept_vec & lane_mask
    new_caches = inject(cfg, main_caches, thought_caches, accept, beta)
    return new_caches, accept, score


def inject(cfg: ModelConfig, main_caches, thought_caches, accept, beta: float = 0.3):
    """Dispatch injection across the whole stack. Both cache trees must come
    from the same cfg (same group structure)."""
    new_groups = []
    for grp, m, t in zip(cfg.layer_groups(), main_caches.groups, thought_caches.groups):
        if grp.kind == "attn":
            if isinstance(m, cache_lib.MLACache):
                new_groups.append(inject_mla(m, t, accept))
            elif isinstance(m, cache_lib.SynapseCache):
                new_groups.append(inject_synapse(m, t, accept))
            else:
                new_groups.append(inject_full(m, t, accept))
        else:
            new_groups.append(blend_state(m, t, accept, beta))
    shared = main_caches.shared
    if shared is not None and thought_caches.shared is not None:
        if isinstance(shared, cache_lib.SynapseCache):
            shared = inject_synapse(shared, thought_caches.shared, accept)
        else:
            shared = inject_full(shared, thought_caches.shared, accept)
    return model_lib.ModelCaches(groups=tuple(new_groups), shared=shared)
